"""Device prefetcher + shape bucketing (ISSUE 4 acceptance):

- prefetcher unit behavior: delivery order, clean shutdown,
  producer-exception propagation (stub trainer, no XLA);
- with --prefetch-to-device the training thread performs NO host-side
  batch prep between dispatches (instrumented hooks), and the prefetched
  run is bit-identical to the synchronous one;
- 2 CPU processes: the off-thread KV slot plan agrees with the
  synchronous psum plan under epoch tails, empty peers, and dummy slots,
  and the pipelined run's params stay bit-for-bit equal to the
  synchronous run's on every host;
- --length-bucket bounds the number of distinct batch geometries — and
  therefore compiled train-step programs — by the bucket count over a
  length-skewed synthetic dataset;
- CLI recompile-budget smoke: a tiny bucketed+prefetched BERT run reports
  ``prefetch_wall`` and logs zero 'recompile after warmup' warnings
  (greppable by the CI step).
"""

import os
import subprocess
import sys
import time
import types
from argparse import Namespace

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from unicore_tpu.data import data_utils, iterators  # noqa: E402
from unicore_tpu.data.prefetch import (  # noqa: E402
    DevicePrefetcher,
    PreparedUpdate,
    RawUpdate,
    plan_slot_modes,
)


# ---------------------------------------------------------------------------
# unit: ordering / shutdown / exception propagation (stub trainer, no XLA)
# ---------------------------------------------------------------------------


class _StubTrainer:
    """The minimal surface DevicePrefetcher needs, single-host."""

    mesh = types.SimpleNamespace(shape={"data": 1})

    def __init__(self):
        self.prepared = []

    @staticmethod
    def _is_empty(sample):
        return sample is None or (
            hasattr(sample, "__len__") and len(sample) == 0
        )

    def _local_sig(self, sample):
        return None if self._is_empty(sample) else ("sig", len(sample))

    def prepare_prefetched(self, samples, modes, sigs):
        self.prepared.append(samples)
        return "single", samples[0], 1.0


def _groups(n, payload=lambda k: {"k": k}):
    return [[payload(k)] for k in range(n)]


def test_prefetcher_delivers_in_order():
    stub = _StubTrainer()
    src = iterators.CountingIterator(iter(_groups(7)), start=0, total=7)
    pf = DevicePrefetcher(stub, src, epoch=1).start()
    items = list(pf)
    pf.close()
    assert [it.seq for it in items] == list(range(7))
    # first item of the epoch is the synchronous fallback (TrainState init
    # + dummy caching happen on the training thread); the rest prefetch
    assert isinstance(items[0], RawUpdate)
    assert all(isinstance(it, PreparedUpdate) for it in items[1:])
    assert [it.data["k"] for it in items[1:]] == list(range(1, 7))
    assert pf.prefetched_updates == 6 and pf.fallback_updates == 1
    assert not pf.has_next() and pf.end_of_epoch()


def test_prefetcher_clean_shutdown_mid_stream():
    stub = _StubTrainer()

    def slow():
        for k in range(1000):
            time.sleep(0.01)
            yield [{"k": k}]

    src = iterators.CountingIterator(slow(), start=0, total=1000)
    pf = DevicePrefetcher(stub, src, epoch=1).start()
    first = next(pf)
    assert first.seq == 0
    t0 = time.monotonic()
    pf.close()
    assert time.monotonic() - t0 < 10.0, "close() did not return promptly"
    assert not pf._thread.is_alive(), "producer thread still running"


def test_prefetcher_propagates_producer_exception():
    stub = _StubTrainer()

    def broken():
        yield [{"k": 0}]
        yield [{"k": 1}]
        raise ValueError("loader exploded")

    src = iterators.CountingIterator(broken(), start=0, total=5)
    pf = DevicePrefetcher(stub, src, epoch=1).start()
    got = [next(pf), next(pf)]
    assert [g.seq for g in got] == [0, 1]
    with pytest.raises(ValueError, match="loader exploded"):
        next(pf)
    pf.close()


def test_prefetcher_take_propagates_to_source():
    """take(n) caps the producer's source too (CountingIterator contract):
    the producer must not keep planning/transferring past the cap."""
    stub = _StubTrainer()
    src = iterators.CountingIterator(iter(_groups(10)), start=0, total=10)
    pf = DevicePrefetcher(stub, src, epoch=1)
    pf.take(4)
    assert src.total == 4
    pf.start()
    items = list(pf)
    pf.close()
    assert [it.seq for it in items] == list(range(4))
    # the producer never built anything past the cap (item 0 is the raw
    # first-update fallback, so 3 prepared items cover seqs 1..3)
    assert len(stub.prepared) == 3


def test_prefetcher_empty_slot_falls_back_raw():
    """Single-host tails (empty micro-slots) take the RawUpdate path —
    the dummy-batch protocol stays on the training thread."""
    stub = _StubTrainer()
    groups = [[{"k": 0}], [{"k": 1}], [{}], [{"k": 3}]]
    src = iterators.CountingIterator(iter(groups), start=0, total=4)
    pf = DevicePrefetcher(stub, src, epoch=1).start()
    items = list(pf)
    pf.close()
    kinds = [type(it).__name__ for it in items]
    assert kinds == ["RawUpdate", "PreparedUpdate", "RawUpdate",
                     "PreparedUpdate"]
    assert "empty" in items[2].reason


def test_plan_slot_modes_matrix():
    """The pure mode agreement shared by the sync psum plan and the KV
    exchange: shard / gather / dummy decisions."""
    sig = ("tree", (((4, 16), "int32"),))
    odd = ("tree", (((3, 16), "int32"),))
    # both hosts same 4-row batch over a 2-way data axis -> shard
    assert plan_slot_modes([[sig], [sig]], 2, 2) == ["shard"]
    # divergent shapes -> gather; one empty -> gather; both empty -> dummy
    assert plan_slot_modes([[sig], [odd]], 2, 2) == ["gather"]
    assert plan_slot_modes([[sig], [None]], 2, 2) == ["gather"]
    assert plan_slot_modes([[None], [None]], 2, 2) == ["dummy"]
    # rows not divisible by the local shard count (4-way data axis over 2
    # hosts -> 2 shards/host; 3 rows don't divide) -> gather
    assert plan_slot_modes([[odd], [odd]], 4, 2) == ["gather"]
    # scalar-leaf batches can't row-shard
    assert plan_slot_modes([["unshardable"], ["unshardable"]], 2, 2) == [
        "gather"
    ]
    # multi-slot plans decide per slot
    assert plan_slot_modes([[sig, None], [sig, None]], 2, 2) == [
        "shard", "dummy",
    ]


# ---------------------------------------------------------------------------
# integration: prefetched training == synchronous training (single host)
# ---------------------------------------------------------------------------


def _mk_args(**kw):
    d = dict(
        seed=1, bf16=False, fp16=False, bf16_sr=False,
        allreduce_fp32_grad=False, fp16_init_scale=4, fp16_scale_window=None,
        min_loss_scale=1e-4, clip_norm=1.0, per_sample_clip_norm=0.0,
        data_parallel_size=-1, model_parallel_size=1, seq_parallel_size=1,
        pipeline_parallel_size=1, expert_parallel_size=1,
        zero_shard_optimizer=False, optimizer="adam", lr_scheduler="fixed",
        lr=[1e-3], adam_betas="(0.9, 0.999)", adam_eps=1e-8, weight_decay=0.0,
        force_anneal=None, lr_shrink=0.1, warmup_updates=0, ema_decay=-1.0,
        validate_with_ema=False, max_update=100, update_freq=[1],
        donate_train_state=False, prefetch_to_device=True,
        compile_warmup_updates=3,
    )
    d.update(kw)
    return Namespace(**d)


def _mk_trainer(args):
    from unicore_tpu.losses import LOSS_REGISTRY
    from unicore_tpu.models.bert import BertModel
    from unicore_tpu.tasks.unicore_task import UnicoreTask
    from unicore_tpu.trainer import Trainer

    class T(UnicoreTask):
        class _D:
            def pad(self):
                return 1

        dictionary = _D()

    model = BertModel(
        vocab_size=64, padding_idx=1, encoder_layers=1, encoder_embed_dim=32,
        encoder_ffn_embed_dim=64, encoder_attention_heads=4, max_seq_len=64,
        post_ln=True, dropout=0.0, emb_dropout=0.0, attention_dropout=0.0,
    )
    return Trainer(args, T(args), model, LOSS_REGISTRY["masked_lm"](T(args)))


def _batch(seed, rows=8, width=32):
    r = np.random.RandomState(seed)
    tok = r.randint(4, 64, size=(rows, width)).astype(np.int64)
    tgt = np.where(r.rand(rows, width) < 0.2, tok, 1).astype(np.int64)
    return {"net_input": {"src_tokens": tok}, "target": tgt}


def _params(trainer):
    import jax

    leaves = jax.tree_util.tree_leaves(jax.device_get(trainer.state["params"]))
    return [np.asarray(l) for l in leaves]


@pytest.mark.parametrize("widths", [(32,), (32, 32), (32, 48)],
                         ids=["single", "scan", "micro"])
def test_prefetched_training_is_bit_identical(widths):
    """One batch, equal shapes (one scanned program) and mixed shapes
    (micro-steps and an apply): every kind of prepared update."""
    uf = len(widths)
    groups = lambda: [  # noqa: E731 — rebuilt per run, same data
        [_batch(10 * i + j, width=w) for j, w in enumerate(widths)]
        for i in range(5)
    ]

    sync = _mk_trainer(_mk_args(update_freq=[uf]))
    for g in groups():
        sync.train_step(g)

    pre = _mk_trainer(_mk_args(update_freq=[uf]))
    src = iterators.CountingIterator(iter(groups()), start=0, total=5)
    pf = DevicePrefetcher(pre, src, epoch=1).start()
    consumed = [0, 0]
    for item in pf:
        consumed[isinstance(item, PreparedUpdate)] += 1
        pre.train_step(item)
    pf.close()

    # the acceptance hook: zero host-side batch prep ran on the training
    # thread while it consumed prepared updates
    assert pre._hot_thread_preps == 0
    assert consumed == [1, 4]  # first update raw, the rest prefetched
    for a, b in zip(_params(sync), _params(pre)):
        assert np.array_equal(a, b), "prefetched run diverged from sync run"
    # same compiled-program count either way: the prefetcher feeds the
    # exact layouts the synchronous path would have
    assert pre._count_compiled_programs() == sync._count_compiled_programs()


def test_prefetcher_reports_consumed_position():
    """state_dict position under prefetch reflects what was TRAINED, not
    the producer's read-ahead (mid-epoch resume must not skip data)."""
    tr = _mk_trainer(_mk_args())
    groups = [[_batch(i)] for i in range(6)]
    src = iterators.CountingIterator(iter(groups), start=0, total=6)

    class _EpochItr:
        iterations_in_epoch = 0
        position_source = None

    epoch_itr = _EpochItr()
    pf = DevicePrefetcher(tr, src, epoch=1)
    pf.attach_epoch_itr(epoch_itr)
    pf.start()
    assert epoch_itr.position_source is pf
    tr.train_step(next(pf))
    tr.train_step(next(pf))
    # producer has read ahead of the 2 consumed updates; the override
    # reports the consumed position regardless
    assert pf.iterations_in_epoch == 2
    assert not pf.end_of_epoch()
    for item in pf:
        tr.train_step(item)
    assert pf.iterations_in_epoch == 6 and pf.end_of_epoch()
    pf.close()
    assert epoch_itr.position_source is None


def test_maybe_prefetch_honors_prefetch_depth():
    """--prefetch-depth governs the device read-ahead depth (deliberately
    NOT --data-buffer-size, whose default of 10 is a host-loader knob and
    would park 10 prepared updates in HBM)."""
    tr = _mk_trainer(_mk_args(prefetch_depth=5))
    src = iterators.CountingIterator(iter([[_batch(i)] for i in range(3)]),
                                     start=0, total=3)
    pf = tr.maybe_prefetch(src)
    try:
        assert isinstance(pf, DevicePrefetcher)
        assert pf._queue.maxsize == 5
    finally:
        pf.close()


# ---------------------------------------------------------------------------
# shape bucketing
# ---------------------------------------------------------------------------


def test_compute_length_buckets():
    # even spacing without sizes; rounded to the multiple; covers max_len
    assert data_utils.compute_length_buckets(3, 64, multiple=8) == (24, 48, 64)
    assert data_utils.compute_length_buckets(1, 60, multiple=8) == (64,)
    assert data_utils.compute_length_buckets(0, 64, multiple=8) is None
    # quantile spacing with a skewed distribution concentrates edges where
    # the mass is; edges dedup so the count may shrink
    sizes = [8] * 90 + [60] * 10
    got = data_utils.compute_length_buckets(4, 64, multiple=8, sizes=sizes)
    assert got is not None and got[0] == 8 and got[-1] == 64
    assert len(got) <= 4
    # bucket_for: smallest covering edge; None past the top edge
    assert data_utils.bucket_for(9, (24, 48, 64)) == 24
    assert data_utils.bucket_for(48, (24, 48, 64)) == 48
    assert data_utils.bucket_for(65, (24, 48, 64)) is None


def test_bucketed_collater_bounds_geometry_count():
    """Over a length-skewed synthetic dataset, the padded widths the
    collater emits stay within the bucket set."""
    buckets = data_utils.compute_length_buckets(3, 64, multiple=8)
    rng = np.random.RandomState(0)
    # skewed: mostly short, a long tail — many distinct raw lengths
    lengths = np.concatenate([
        rng.randint(5, 20, size=40), rng.randint(40, 65, size=10)
    ])
    widths = set()
    for i in range(0, len(lengths), 4):
        vals = [np.full(l, 7, dtype=np.int64) for l in lengths[i:i + 4]]
        out = data_utils.collate_tokens(
            vals, pad_idx=1, pad_to_multiple=8, pad_to_buckets=buckets
        )
        widths.add(out.shape[1])
    assert widths <= set(buckets)
    assert len(widths) <= len(buckets)
    # without buckets the same stream produces MORE distinct widths
    plain = set()
    for i in range(0, len(lengths), 4):
        vals = [np.full(l, 7, dtype=np.int64) for l in lengths[i:i + 4]]
        plain.add(
            data_utils.collate_tokens(vals, 1, pad_to_multiple=8).shape[1]
        )
    assert len(plain) > len(widths)


def test_batch_by_size_groups_by_bucket():
    """With sizes + bucket_edges, full batches are homogeneous per bucket
    so each pads to its own edge instead of the stream's longest sample."""
    sizes = np.array([10, 50, 12, 60, 9, 55, 14, 58])
    indices = np.arange(8)
    edges = (16, 64)
    batches = data_utils.batch_by_size(
        indices, batch_size=2, sizes=sizes, bucket_edges=edges
    )
    for b in batches:
        bucket_ids = {data_utils.bucket_for(sizes[i], edges) for i in b}
        assert len(bucket_ids) == 1, f"mixed-bucket batch {b}"
    # every index is batched exactly once
    assert sorted(i for b in batches for i in b) == list(range(8))
    # without sizes the call degrades to plain chunking
    plain = data_utils.batch_by_size(indices, batch_size=2)
    assert sorted(i for b in plain for i in b) == list(range(8))


def test_batch_by_size_bucket_tails_merge():
    """Per-bucket remainders merge into shared tail batches: at most ONE
    odd-sized batch overall (not one per bucket), and every full-size
    batch pads to an edge that full batches already use — so tails can't
    mint geometries past the bucket count."""
    # bucket 0: 5 members, bucket 1: 3 members -> remainders 1 and 1
    sizes = np.array([10, 9, 12, 14, 11, 50, 60, 55])
    indices = np.arange(8)
    edges = (16, 64)
    batches = data_utils.batch_by_size(
        indices, batch_size=2, sizes=sizes, bucket_edges=edges
    )
    assert sorted(i for b in batches for i in b) == list(range(8))
    odd = [b for b in batches if len(b) != 2]
    assert len(odd) <= 1, f"more than one odd-sized tail: {batches}"
    # geometry bound: (rows, covering edge) pairs <= bucket count + 1 tail
    geoms = {
        (len(b), data_utils.bucket_for(max(sizes[i] for i in b), edges))
        for b in batches
    }
    assert len(geoms) <= len(edges) + 1


def test_task_iterator_engages_bucket_partition():
    """Production wiring: a dataset that reports ordered_sizes() gets
    quantile edges AND per-bucket homogeneous batches straight through
    UnicoreTask.get_batch_iterator; one without stays on plain chunking
    (the collater's bucket snap alone bounds compiles)."""
    from unicore_tpu.data import UnicoreDataset
    from unicore_tpu.tasks.unicore_task import UnicoreTask

    rng = np.random.RandomState(3)
    sizes = np.concatenate([rng.randint(5, 17, 24), rng.randint(40, 65, 8)])

    class _SizedDataset(UnicoreDataset):
        def __init__(self, with_sizes):
            super().__init__()
            self.with_sizes = with_sizes

        def __len__(self):
            return len(sizes)

        def __getitem__(self, index):
            return np.full(sizes[index], 7, dtype=np.int64)

        def collater(self, samples):
            return data_utils.collate_tokens(samples, pad_idx=1)

        def ordered_sizes(self):
            return sizes if self.with_sizes else None

    task = UnicoreTask(Namespace(length_bucket=3, seq_pad_multiple=8))
    itr = task.get_batch_iterator(_SizedDataset(True), batch_size=4)
    edges = task.length_bucket_edges()
    # quantile edges: the short-mass edge sits far below even spacing
    assert edges is not None and edges[0] <= 24 and edges[-1] >= max(sizes)
    # per-bucket remainders merge: at most one odd-sized batch overall,
    # and the (rows, covering-edge) geometry count stays <= buckets + tail
    odd = [b for b in itr.frozen_batches if len(b) != 4]
    assert len(odd) <= 1, f"more than one odd-sized tail: {odd}"
    geoms = {
        (len(b), data_utils.bucket_for(max(sizes[i] for i in b), edges))
        for b in itr.frozen_batches
    }
    assert len(geoms) <= len(edges) + 1
    assert sorted(i for b in itr.frozen_batches for i in b) == list(
        range(len(sizes))
    )

    plain_task = UnicoreTask(Namespace(length_bucket=3, seq_pad_multiple=8))
    plain = plain_task.get_batch_iterator(_SizedDataset(False), batch_size=4)
    assert [list(b) for b in plain.frozen_batches] == [
        list(range(i, i + 4)) for i in range(0, len(sizes), 4)
    ]


def test_bucketed_run_compiles_at_most_one_program_per_bucket():
    """Acceptance: a length-skewed run compiles <= bucket-count train-step
    programs, and the count stays flat past --compile-warmup-updates."""
    buckets = data_utils.compute_length_buckets(3, 64, multiple=8)
    tr = _mk_trainer(_mk_args(compile_warmup_updates=8))
    rng = np.random.RandomState(3)
    # every bucket shows up during warmup (44/61/17 -> 48/64/24), then a
    # skewed tail of many distinct raw lengths
    skewed = [44, 61, 17] + list(rng.randint(5, 20, size=6)) + [30, 12, 59]
    for step, raw_len in enumerate(skewed):
        width = data_utils.bucket_for(
            data_utils.pad_to_multiple_size(int(raw_len), 8), buckets
        )
        tr.train_step([_batch(step, rows=8, width=width)])
    assert tr._count_compiled_programs() <= len(buckets)
    assert tr._recompile_count <= len(buckets)
    after_warmup = tr._count_compiled_programs()
    # replay the same geometry mix: no new programs after warmup
    for step, raw_len in enumerate(skewed):
        width = data_utils.bucket_for(
            data_utils.pad_to_multiple_size(int(raw_len), 8), buckets
        )
        tr.train_step([_batch(100 + step, rows=8, width=width)])
    assert tr._count_compiled_programs() == after_warmup


# ---------------------------------------------------------------------------
# 2 CPU processes: pipelined slot plan == synchronous plan, bit-for-bit
# ---------------------------------------------------------------------------

import test_multihost as tm  # noqa: E402  (shared 2-proc harness)

PREFETCH_WORKER = tm._preamble(2) + tm._TRAIN_SETUP.replace(
    "__DATA_PAR__", "-1"
).replace("__MODEL_PAR__", "1") + r"""
from unicore_tpu.data import iterators
from unicore_tpu.data.prefetch import (
    DevicePrefetcher, PreparedUpdate, RawUpdate,
)
from unicore_tpu.trainer import Trainer

def groups():
    # epoch shapes covering every slot mode: shard steps, a fused-scan
    # step, an epoch tail (divergent rows -> gather), an exhausted peer
    # (rank 0 empty -> gather), and a both-empty dummy slot
    return [
        [make_batch(100 + rank, 4)],                      # first: raw
        [make_batch(110 + rank, 4)],                      # shard
        [make_batch(120 + rank, 4), make_batch(130 + rank, 4)],  # scan
        [make_batch(200 + rank, 3 + rank)],               # tail -> gather
        [make_batch(300, 4) if rank == 1 else {}],        # empty peer
        [{}],                                             # dummy
        [make_batch(400 + rank, 4)],                      # shard again
    ]

# --- synchronous reference run (also records the agreed plans) -----------
sync_plans = []
for gs in groups():
    modes, sigs, flags = trainer._plan_slots(gs)
    sync_plans.append(modes)
    trainer.train_step(gs)
sync_hash = param_hash(trainer._state["params"])

# --- pipelined run: same data through the device prefetcher --------------
trainer2 = Trainer(args, task, ge._flagship(
    vocab=128, layers=1, dim=64, heads=2, ffn=128, max_seq=16), loss)
src = iterators.CountingIterator(iter(groups()), start=0, total=7)
pf = DevicePrefetcher(trainer2, src, epoch=1).start()
pf_plans, kinds = [], []
for item in pf:
    pf_plans.append(item.modes)
    kinds.append(type(item).__name__)
    trainer2.train_step(item)
pf.close()

# the KV-exchanged plan agrees with the synchronous psum plan, slot for
# slot, including the epoch tail / empty-peer / dummy updates
assert pf_plans == sync_plans, (pf_plans, sync_plans)
assert sync_plans[3] == ["gather"] and sync_plans[5] == ["dummy"], sync_plans
# shard-only updates prefetched; everything else (and the first) fell back
assert kinds == ["RawUpdate", "PreparedUpdate", "PreparedUpdate",
                 "RawUpdate", "RawUpdate", "RawUpdate",
                 "PreparedUpdate"], kinds
assert trainer2._hot_thread_preps == 0, trainer2._hot_thread_preps

# bit-for-bit: pipelined == synchronous on this host, and across hosts
pf_hash = param_hash(trainer2._state["params"])
assert pf_hash == sync_hash, "pipelined run diverged from synchronous run"
hashes = du.all_gather_list(pf_hash)
assert hashes[0] == hashes[1], "params diverged across hosts"

print(f"RANK{rank}_OK", flush=True)
"""


@pytest.mark.slow
def test_two_process_prefetch_plan_agreement(tmp_path):
    """Acceptance: on 2 CPU processes the pipelined slot plan agrees
    bit-for-bit with the synchronous plan under epoch tails and dummy
    slots, and the trained params match the synchronous run exactly."""
    tm._run_two_procs(PREFETCH_WORKER, timeout=420)


# ---------------------------------------------------------------------------
# CLI recompile-budget smoke (also driven by CI's grep step)
# ---------------------------------------------------------------------------

from test_e2e_train import _JAX_CACHE, CLI_TIMEOUT, RUNNER  # noqa: E402


@pytest.fixture(scope="module")
def cli_data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("prefetch_bert_data")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "examples", "bert", "make_example_data.py"),
            # the 8-device mesh scales --batch-size 8 to 64 rows/host-batch:
            # 768 docs = 12 full batches per epoch, no tail
            str(d), "768", "16",
        ],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    return d


@pytest.mark.slow
def test_cli_recompile_budget(cli_data_dir, tmp_path, capsys):
    """Tiny BERT CPU run with bucketing + prefetch on: ``prefetch_wall``
    must be reported in the metrics log and ZERO 'recompile after warmup'
    warnings may fire.  Output is echoed so the CI smoke step can grep
    it (run with ``-s``)."""
    argv = [
        str(cli_data_dir),
        "--task", "bert", "--loss", "masked_lm", "--arch", "bert_tiny",
        "--optimizer", "adam", "--lr-scheduler", "fixed", "--lr", "1e-3",
        "--max-update", "12", "--max-epoch", "4", "--batch-size", "8",
        "--max-seq-len", "64", "--length-bucket", "3",
        "--prefetch-to-device", "--compile-warmup-updates", "6",
        "--jax-compilation-cache-dir", str(tmp_path / "xla_cache"),
        "--log-interval", "1", "--log-format", "simple",
        "--disable-validation", "--no-progress-bar",
        "--save-dir", str(tmp_path / "ckpt"),
        "--tmp-save-dir", str(tmp_path / "tmp"),
        "--num-workers", "0", "--seed", "1",
        "--required-batch-size-multiple", "1",
    ]
    proc = subprocess.run(
        [sys.executable, "-c",
         RUNNER.format(repo=REPO, argv=argv, cache=_JAX_CACHE)],
        capture_output=True, text=True, timeout=CLI_TIMEOUT, cwd=REPO,
    )
    out = proc.stdout + proc.stderr
    with capsys.disabled():
        print(out)
    assert proc.returncode == 0, out[-4000:]
    assert "num_updates: 12" in out
    assert "prefetch_wall" in out, "prefetch_wall metric not reported"
    assert "recompiles" in out, "recompiles metric not reported"
    assert "recompile after warmup" not in out, (
        "bucketed run recompiled past --compile-warmup-updates"
    )
    # the persistent compile cache was actually exercised
    assert os.path.isdir(tmp_path / "xla_cache")
    assert len(os.listdir(tmp_path / "xla_cache")) > 0
