"""Elastic run control plane (ISSUE 6): heartbeat leases, host-loss
verdicts, the exit-code taxonomy, the shared retry/deadline surface, and
the supervised restart loop — proven from the pure state machines up to a
2-process kill-one-host chaos run that detects, re-forms, and finishes."""

import os
import socket
import subprocess
import sys
import time
from argparse import Namespace

import numpy as np
import pytest

from unicore_tpu.distributed import chaos, elastic, guard
from unicore_tpu.utils import retry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_state():
    yield
    elastic.stop()
    chaos.reset()
    guard.reset()


# ---------------------------------------------------------------------------
# exit-code taxonomy
# ---------------------------------------------------------------------------


def test_exit_code_taxonomy_maps_every_terminal_error():
    from unicore_tpu.checkpoint.durable import CheckpointWriteError
    from unicore_tpu.checkpoint.format import CorruptCheckpointError
    from unicore_tpu.data.iterators import DataStallError
    from unicore_tpu.data.prefetch import PrefetchError
    from unicore_tpu.health.sentinel import TrainingHealthError

    cases = [
        (elastic.HostLossError("x"), elastic.EXIT_HOST_LOSS),
        (elastic.ElasticError("x"), elastic.EXIT_CONTROL_PLANE),
        (guard.CollectiveTimeoutError("x"), elastic.EXIT_COLLECTIVE_TIMEOUT),
        (guard.ConsistencyError("x"), elastic.EXIT_CONSISTENCY),
        (guard.DesyncError("x"), elastic.EXIT_CONSISTENCY),  # subclass
        (retry.KVTimeoutError("x"), elastic.EXIT_CONTROL_PLANE),
        (DataStallError("x"), elastic.EXIT_DATA_STALL),
        (PrefetchError("x"), elastic.EXIT_PREFETCH),
        (CorruptCheckpointError("x"), elastic.EXIT_CORRUPT_CHECKPOINT),
        (CheckpointWriteError("x"), elastic.EXIT_CHECKPOINT_WRITE),
        (TrainingHealthError("x"), elastic.EXIT_TRAINING_HEALTH),
        (ValueError("x"), elastic.EXIT_UNCAUGHT),
    ]
    for err, want in cases:
        assert elastic.exit_code(err) == want, type(err).__name__
    # every taxonomy code is named and has a stable retryable verdict
    for code, _ in [(c, n) for c, n in elastic.EXIT_CODE_NAMES.items()]:
        assert isinstance(elastic.is_retryable_exit(code), bool)


def test_retryable_exit_set_is_environmental_failures_only():
    assert elastic.is_retryable_exit(elastic.EXIT_HOST_LOSS)
    assert elastic.is_retryable_exit(elastic.EXIT_COLLECTIVE_TIMEOUT)
    assert elastic.is_retryable_exit(elastic.EXIT_DATA_STALL)
    assert elastic.is_retryable_exit(elastic.EXIT_CONTROL_PLANE)
    assert elastic.is_retryable_exit(elastic.EXIT_WORKER_KILLED)
    assert elastic.is_retryable_exit(-9)  # SIGKILL'd child
    # run-state failures must never be retried into the same wall
    assert not elastic.is_retryable_exit(elastic.EXIT_CONSISTENCY)
    assert not elastic.is_retryable_exit(elastic.EXIT_CORRUPT_CHECKPOINT)
    assert not elastic.is_retryable_exit(elastic.EXIT_TRAINING_HEALTH)
    assert not elastic.is_retryable_exit(elastic.EXIT_UNCAUGHT)


def test_chaos_host_loss_exit_code_matches_taxonomy():
    """chaos hard-exits with the code the supervisor treats as a killed
    worker; the two constants live in different modules (importing either
    from the other would be a cycle) so this pin is the contract."""
    assert chaos.HOST_LOSS_EXIT_CODE == elastic.EXIT_WORKER_KILLED


# ---------------------------------------------------------------------------
# heartbeat leases
# ---------------------------------------------------------------------------


def test_lease_roundtrip_and_garbage_rejected():
    lease = elastic.Lease(epoch=3, seq=17, step=420, wall=1234.5)
    got = elastic.decode_lease(elastic.encode_lease(lease))
    assert got == lease
    with pytest.raises(ValueError):
        elastic.decode_lease("not a lease")
    with pytest.raises(ValueError):
        elastic.decode_lease("uctp-hb1|1|2")


def _table(timeout=5.0, epoch=0, peers=(1,), now=100.0):
    return elastic.LeaseTable(peers, epoch, timeout, now)


def _lease(epoch=0, seq=1, step=0):
    return elastic.Lease(epoch, seq, step, 0.0)


def test_lease_table_advancing_peer_is_healthy():
    t = _table()
    assert t.observe(1, _lease(seq=1), 101.0) is None
    assert t.sweep(104.0) is None  # last advance at 101, timeout 5
    assert t.observe(1, _lease(seq=2), 105.0) is None
    assert t.sweep(109.0) is None  # advanced at 105


def test_lease_table_expired_lease_names_the_rank():
    t = _table()
    t.observe(1, _lease(seq=1), 101.0)
    # the same seq re-read is NOT an advance: silence since 101
    t.observe(1, _lease(seq=1), 106.5)
    verdict = t.sweep(106.5)
    assert verdict is not None and verdict.kind == "host-loss"
    assert verdict.ranks == [1]
    assert "rank 1" in verdict.message
    assert "lease expired" in verdict.message
    assert "5.5s" in verdict.message  # the measured silence is named
    assert isinstance(verdict.error(), elastic.HostLossError)


def test_lease_table_never_published_peer_expires_from_start():
    t = _table(now=100.0)
    # service answers, but the peer never wrote a key
    t.observe(1, retry.ABSENT, 103.0)
    assert t.sweep(104.0) is None
    t.observe(1, retry.ABSENT, 106.0)
    verdict = t.sweep(106.0)
    assert verdict is not None and verdict.kind == "host-loss"


def test_lease_table_stale_epoch_peer_is_named():
    t = _table(epoch=2)
    verdict = t.observe(1, _lease(epoch=1, seq=9), 101.0)
    assert verdict is not None and verdict.kind == "stale-host"
    assert "STALE membership epoch 1" in verdict.message
    assert isinstance(verdict.error(), elastic.HostLossError)


def test_lease_table_newer_epoch_means_we_are_stale():
    t = _table(epoch=0)
    verdict = t.observe(1, _lease(epoch=2, seq=1), 101.0)
    assert verdict is not None and verdict.kind == "self-stale"
    assert "THIS host is the stale one" in verdict.message
    assert isinstance(verdict.error(), guard.ConsistencyError)
    # the newer-epoch peer is the HEALTHY one: it must NOT be named lost
    # (that would invert the diagnosis in the state file + stop reason)
    assert verdict.ranks == []
    assert verdict.stop_reason() == "SELF-STALE"


def test_lease_table_mass_silence_is_control_plane_not_split_brain():
    """ALL peers silent at once reads as a service partition, not N
    simultaneous host losses — a mass host-loss verdict would let each
    partition side re-form without the others and train independently."""
    t = _table(timeout=5.0, peers=(1, 2, 3), now=100.0)
    for r in (1, 2, 3):
        t.observe(r, _lease(seq=1), 101.0)
    # the service keeps ANSWERING (absent/frozen leases) — only the peers
    # look dead, and all of them at once
    for r in (1, 2, 3):
        t.observe(r, retry.ABSENT, 106.6)
    verdict = t.sweep(106.6)
    assert verdict is not None and verdict.kind == "control-plane"
    assert "splitting the brain" in verdict.message
    # ... but ONE silent peer among three is a genuine host loss (its
    # lease is still OBSERVED each round — frozen, not missing)
    t2 = _table(timeout=5.0, peers=(1, 2, 3), now=100.0)
    for r in (1, 2, 3):
        t2.observe(r, _lease(seq=1), 101.0)
    t2.observe(1, _lease(seq=1), 106.5)  # frozen: seq never advanced
    for r in (2, 3):
        t2.observe(r, _lease(seq=2), 106.5)
    verdict = t2.sweep(106.5)
    assert verdict is not None and verdict.kind == "host-loss"
    assert verdict.ranks == [1]


def test_lease_table_service_silence_is_not_peer_silence():
    """An unreachable KV store must not age any peer's lease (a short
    service blip would otherwise mint host-loss verdicts for every rank
    at once); a LONG outage becomes its own control-plane verdict."""
    t = _table(timeout=5.0, now=100.0)
    t.observe(1, _lease(seq=1), 101.0)
    # 4s of outage: no evidence about the peer, no verdict either way
    for now in (102.0, 103.0, 104.0, 105.0):
        assert t.observe(1, retry.UNREACHABLE, now) is None
    assert t.sweep(105.0) is None  # peer silence clock did NOT run
    # hmm — peer last advanced at 101 and 105-101 < 5: also no verdict
    # once the service answers again and the lease advanced, all healthy
    t.observe(1, _lease(seq=2), 105.5)
    assert t.sweep(105.5) is None
    # a LONG outage (no successful observation past the timeout) is a
    # control-plane verdict, not a host-loss one
    for now in (106.0, 108.0, 110.0, 111.0):
        t.observe(1, retry.UNREACHABLE, now)
    verdict = t.sweep(111.0)
    assert verdict is not None and verdict.kind == "control-plane"
    assert isinstance(verdict.error(), elastic.ElasticError)
    assert "unreachable" in verdict.message


def test_lease_table_outage_shorter_than_timeout_never_false_trips():
    t = _table(timeout=5.0, now=100.0)
    t.observe(1, _lease(seq=1), 101.0)
    for now in (102.0, 103.0, 104.0):
        t.observe(1, retry.UNREACHABLE, now)
        assert t.sweep(now) is None
    t.observe(1, _lease(seq=2), 104.5)
    assert t.sweep(109.0) is None


def test_verdict_json_roundtrip_marks_adoption():
    v = elastic.Verdict("host-loss", [1, 3], "rank 1 gone; rank 3 gone")
    got = elastic.Verdict.from_json(v.to_json())
    assert (got.kind, got.ranks, got.message) == (
        "host-loss", [1, 3], "rank 1 gone; rank 3 gone"
    )
    assert got.adopted  # a deserialized verdict came from a peer


# ---------------------------------------------------------------------------
# shared retry surface
# ---------------------------------------------------------------------------


def test_retry_call_retries_then_succeeds_with_exponential_delays():
    calls = {"n": 0}
    delays = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("blip")
        return "ok"

    got = retry.retry_call(
        flaky,
        retry.RetryPolicy(attempts=4, backoff=0.5),
        sleep=delays.append,
    )
    assert got == "ok" and calls["n"] == 3
    assert delays == [0.5, 1.0]  # backoff * 2**attempt


def test_retry_call_exhaustion_raises_last_error():
    def always():
        raise OSError("down")

    with pytest.raises(OSError, match="down"):
        retry.retry_call(
            always, retry.RetryPolicy(attempts=3, backoff=0.1),
            sleep=lambda s: None,
        )


def test_retry_call_giveup_short_circuits():
    calls = {"n": 0}

    def fatal():
        calls["n"] += 1
        raise OSError("enospc-ish")

    with pytest.raises(OSError):
        retry.retry_call(
            fatal, retry.RetryPolicy(attempts=5, backoff=0.1),
            giveup=lambda e: True, sleep=lambda s: None,
        )
    assert calls["n"] == 1  # no retries for an error that cannot blip clear


def test_compute_delay_jitter_and_cap_bounds():
    policy = retry.RetryPolicy(backoff=1.0, jitter=0.25, max_delay=8.0)
    lo = retry.compute_delay(policy, 2, rng=lambda: 0.0)
    hi = retry.compute_delay(policy, 2, rng=lambda: 0.999)
    assert lo == 4.0 and 4.0 < hi < 5.0
    # the cap applies before jitter, bounding the worst case
    assert retry.compute_delay(policy, 10, rng=lambda: 0.999) < 8.0 * 1.25


def test_backoff_delay_grows_exponentially_within_jitter_bounds():
    base = 1.0
    for k in range(4):
        d = elastic.backoff_delay(k, base)
        assert base * 2 ** k <= d <= base * 2 ** k * 1.25 + 1e-9
    assert elastic.backoff_delay(20, base) <= 60.0 * 1.25  # capped


class _FakeClock:
    def __init__(self, start=0.0):
        self.now = start

    def __call__(self):
        return self.now

    def sleep(self, secs):
        self.now += secs


class _FakeKV:
    """In-memory stand-in for the coordination-service client."""

    def __init__(self, clock=None):
        self.store = {}
        self.clock = clock

    def key_value_set(self, key, value, allow_overwrite=False):
        self.store[key] = value

    def key_value_delete(self, key):
        self.store.pop(key, None)

    def blocking_key_value_get(self, key, timeout_ms):
        if key in self.store:
            return self.store[key]
        if self.clock is not None:  # burn the slice like the real client
            self.clock.sleep(timeout_ms / 1000.0)
        raise TimeoutError("Deadline Exceeded")


def test_kv_wait_returns_value_and_respects_deadline():
    clock = _FakeClock()
    kv = _FakeKV(clock)
    kv.key_value_set("k", "v")
    assert retry.kv_wait(kv, "k", timeout=1.0, clock=clock,
                         sleep=clock.sleep) == "v"
    t0 = clock.now
    with pytest.raises(retry.KVTimeoutError, match="missing"):
        retry.kv_wait(kv, "missing", timeout=10.0, poll_s=2.0,
                      clock=clock, sleep=clock.sleep)
    assert clock.now - t0 == pytest.approx(10.0, abs=2.0)


def test_kv_wait_abort_and_hold_hooks():
    clock = _FakeClock()
    kv = _FakeKV(clock)

    class Closed(Exception):
        pass

    def abort():
        if clock.now > 3.0:
            raise Closed()

    with pytest.raises(Closed):
        retry.kv_wait(kv, "k", timeout=60.0, poll_s=1.0,
                      should_abort=abort, clock=clock, sleep=clock.sleep)

    # hold_deadline re-arms the budget while our consumer is paused
    clock2 = _FakeClock()
    kv2 = _FakeKV(clock2)
    holds = {"n": 0}

    def hold():
        holds["n"] += 1
        return clock2.now < 15.0  # paused for the first 15s

    with pytest.raises(retry.KVTimeoutError):
        retry.kv_wait(kv2, "k", timeout=5.0, poll_s=1.0,
                      hold_deadline=hold, clock=clock2, sleep=clock2.sleep)
    # the wait survived well past the bare 5s timeout while held
    assert clock2.now == pytest.approx(20.0, abs=2.0)
    assert holds["n"] > 10


def test_kv_outage_chaos_bounds_every_wait_real_time():
    """Acceptance: with kv-outage armed, a KV wait raises at ITS deadline
    — measured with the real clock, no fakes — instead of blocking for
    the outage duration (60s here)."""
    chaos.configure(Namespace(fault_inject="kv-outage:60@0"))
    chaos.note_step(0)
    assert chaos.kv_outage_active()
    t0 = time.monotonic()
    with pytest.raises(retry.KVTimeoutError):
        # client=None proves the outage path never touches the client
        retry.kv_wait(None, "k", timeout=0.6, poll_s=0.1)
    elapsed = time.monotonic() - t0
    assert 0.4 <= elapsed < 3.0, elapsed


def test_kv_fetch_classifies_value_absent_unreachable():
    kv = _FakeKV()
    kv.key_value_set("k", "v")
    assert retry.kv_fetch(kv, "k") == "v"
    assert retry.kv_fetch(kv, "missing") is retry.ABSENT

    class Down:
        def blocking_key_value_get(self, key, timeout_ms):
            raise ConnectionError("refused")

    assert retry.kv_fetch(Down(), "k") is retry.UNREACHABLE
    chaos.configure(Namespace(fault_inject="kv-outage:60@0"))
    chaos.note_step(0)
    assert retry.kv_fetch(kv, "k") is retry.UNREACHABLE


# ---------------------------------------------------------------------------
# chaos kinds
# ---------------------------------------------------------------------------


def test_parse_elastic_chaos_kinds():
    p = chaos.parse_fault_spec("host-loss@6@1")
    assert (p.kind, p.step, p.rank) == ("host-loss", 6, 1)
    p = chaos.parse_fault_spec("heartbeat-stall:12@4@0")
    assert (p.kind, p.param, p.step, p.rank) == ("heartbeat-stall", 12.0, 4, 0)
    p = chaos.parse_fault_spec("kv-outage:5@3")
    assert (p.kind, p.param, p.step) == ("kv-outage", 5.0, 3)
    with pytest.raises(ValueError, match="every rank"):
        chaos.parse_fault_spec("kv-outage@3@1")


def test_kv_outage_gates_on_step_and_window():
    chaos.configure(Namespace(fault_inject="kv-outage:0.2@3"))
    chaos.note_step(2)
    assert not chaos.kv_outage_active()  # before the trigger step
    chaos.note_step(3)
    assert chaos.kv_outage_active()
    time.sleep(0.3)
    assert not chaos.kv_outage_active()  # the window closed


def test_heartbeat_stall_targets_rank_and_windows():
    chaos.configure(Namespace(fault_inject="heartbeat-stall:0.2@2"))
    chaos.note_step(1)
    assert not chaos.heartbeat_stalled()
    chaos.note_step(2)
    assert chaos.heartbeat_stalled()  # single process: last rank is us
    time.sleep(0.3)
    assert not chaos.heartbeat_stalled()


def test_elastic_chaos_kinds_disarm_on_restarted_incarnation(monkeypatch):
    monkeypatch.setenv(elastic.ENV_RESTARTS, "1")
    assert chaos.configure(Namespace(fault_inject="host-loss@6")) is None
    assert chaos.configure(Namespace(fault_inject="kv-outage@6")) is None
    # non-elastic kinds still arm on a restarted incarnation
    assert chaos.configure(
        Namespace(fault_inject="seed-skew@6")
    ) is not None
    monkeypatch.delenv(elastic.ENV_RESTARTS)
    assert chaos.configure(Namespace(fault_inject="host-loss@6")) is not None


# ---------------------------------------------------------------------------
# membership state + staleness
# ---------------------------------------------------------------------------


def test_next_membership_packs_survivors_densely():
    assert elastic.next_membership([0, 2, 3], 2) == (1, 3)
    assert elastic.next_membership([0, 2, 3], 0) == (0, 3)
    assert elastic.next_membership([0, 2, 3], 1) is None  # we were lost
    assert elastic.next_membership([1], 1) == (0, 1)


def test_post_mortem_lost_from_recorded_silences():
    """The supervisor's fallback when the child died before its verdict
    landed: silences >= 75% of the heartbeat timeout count as lost."""
    state = {"suspect_silence": {"1": 3.4, "2": 0.2, "bogus": "x"}}
    lost = elastic.post_mortem_lost(state, hb_timeout=4.0)
    assert list(lost) == [1]
    assert "silent for 3.4s" in lost[1]
    assert elastic.post_mortem_lost(state, hb_timeout=0) == {}
    assert elastic.post_mortem_lost({}, hb_timeout=4.0) == {}


def test_lease_table_silences_are_service_confirmed():
    t = _table(timeout=5.0, peers=(1, 2), now=100.0)
    t.observe(1, _lease(seq=1), 101.0)
    t.observe(2, _lease(seq=1), 101.0)
    t.observe(1, retry.ABSENT, 103.0)       # confirmed silence sample
    t.observe(2, retry.UNREACHABLE, 103.0)  # no evidence: clock frozen
    sil = t.silences()
    assert sil[1] == pytest.approx(2.0)
    assert sil[2] == pytest.approx(0.0)


def test_state_file_roundtrip(tmp_path):
    elastic.write_state(str(tmp_path), rank=1, epoch=2, world=4,
                        survivors=[0, 1, 3], lost={2: "lease expired"})
    state = elastic.read_state(str(tmp_path), 1)
    assert state["membership_epoch"] == 2
    assert state["survivors"] == [0, 1, 3]
    assert state["lost"] == {"2": "lease expired"}
    assert state["written_at"] > 0
    assert elastic.read_state(str(tmp_path), 0) is None  # other rank's file


def test_checkpoint_epoch_staleness_check(monkeypatch, tmp_path):
    # plain (non-elastic) runs may resume anything
    elastic.check_checkpoint_epoch(5)
    # ... INCLUDING when a publisher-only runtime exists (every plain
    # multi-host run has one): a later manual resume of an elastic run's
    # epoch-stamped checkpoint must never be refused
    args = _runtime_args(tmp_path)
    args.elastic = False
    monkeypatch.setattr(
        elastic, "_runtime",
        elastic.HeartbeatRuntime(args, nproc=2, rank=0, client=None),
    )
    elastic.check_checkpoint_epoch(5)
    monkeypatch.setattr(elastic, "_runtime", None)
    monkeypatch.setenv(elastic.ENV_CHILD, "1")
    monkeypatch.setenv(elastic.ENV_EPOCH, "2")
    elastic.check_checkpoint_epoch(None)  # pre-elastic checkpoint: fine
    elastic.check_checkpoint_epoch(1)     # older incarnation: fine (resume)
    elastic.check_checkpoint_epoch(2)     # same incarnation: fine
    with pytest.raises(guard.ConsistencyError, match="STALE HOST"):
        elastic.check_checkpoint_epoch(3)  # future incarnation: refuse


def test_membership_epoch_in_guard_fingerprint(monkeypatch):
    class Stub:
        def get_num_updates(self):
            return 7

        def get_lr(self):
            return 1e-3

        def current_loss_scale(self):
            return 1.0

    g = guard.ConsistencyGuard(Namespace(seed=1,
                                         consistency_check_interval=1))
    monkeypatch.setenv(elastic.ENV_EPOCH, "3")
    assert g.fingerprint(Stub())["membership"] == 3
    # two hosts at different incarnations diverge on the membership field
    fp_a = ("unicore-tpu-consistency-v1",
            {"config": "c", "membership": 3, "step": 7})
    fp_b = ("unicore-tpu-consistency-v1",
            {"config": "c", "membership": 2, "step": 7})
    msg = guard.diagnose_fingerprints([fp_a, fp_b])
    assert msg is not None and "'membership'" in msg


# ---------------------------------------------------------------------------
# heartbeat runtime (threads + fake KV; no XLA, no cluster)
# ---------------------------------------------------------------------------


def _runtime_args(tmp_path, interval=0.05, timeout=1.0):
    return Namespace(
        heartbeat_interval=interval, heartbeat_timeout=timeout,
        elastic=True, save_dir=str(tmp_path),
    )


def test_runtime_publishes_leases_and_detects_silent_peer(tmp_path):
    kv = _FakeKV()
    rt = elastic.HeartbeatRuntime(
        _runtime_args(tmp_path), nproc=2, rank=0, client=kv,
        step_fn=lambda: 42,
    ).start()
    try:
        # our own lease lands and advances
        key0 = rt._hb_key(0)
        deadline = time.monotonic() + 5.0
        while key0 not in kv.store and time.monotonic() < deadline:
            time.sleep(0.01)
        lease = elastic.decode_lease(kv.store[key0])
        assert lease.step == 42 and lease.epoch == 0

        # keep the fake peer alive for a few timeouts: no verdict
        for seq in range(1, 15):
            kv.key_value_set(
                rt._hb_key(1),
                elastic.encode_lease(elastic.Lease(0, seq, 0, 0.0)),
            )
            time.sleep(0.1)
        assert rt.verdict() is None

        # now the peer goes silent: a named verdict within ~timeout
        deadline = time.monotonic() + 5.0
        while rt.verdict() is None and time.monotonic() < deadline:
            time.sleep(0.02)
        verdict = rt.verdict()
        assert verdict is not None and verdict.kind == "host-loss"
        assert verdict.ranks == [1]
        # the verdict was recorded in the KV store for the peers
        assert rt._verdict_key() in kv.store
        # ... drove the agreed-stop machinery ...
        assert guard.stop_requested() == "HOST-LOSS(rank 1)"
        # ... armed the collective early-abort hook ...
        assert isinstance(rt.abort_check(), elastic.HostLossError)
        # ... and left the supervisor a re-formable membership view
        state = elastic.read_state(str(tmp_path), 0)
        assert state["survivors"] == [0] and "1" in state["lost"]
        with pytest.raises(elastic.HostLossError, match="rank 1"):
            rt.raise_if_lost()
    finally:
        rt.stop()


def test_runtime_adopts_peer_recorded_verdict(tmp_path):
    kv = _FakeKV()
    verdict = elastic.Verdict("host-loss", [2], "rank 2 lease expired")
    rt = elastic.HeartbeatRuntime(
        _runtime_args(tmp_path), nproc=3, rank=0, client=kv,
    )
    kv.key_value_set(rt._verdict_key(), verdict.to_json())
    rt.start()
    try:
        deadline = time.monotonic() + 5.0
        while rt.verdict() is None and time.monotonic() < deadline:
            time.sleep(0.02)
        got = rt.verdict()
        assert got is not None and got.adopted and got.ranks == [2]
        state = elastic.read_state(str(tmp_path), 0)
        assert state["survivors"] == [0, 1]
    finally:
        rt.stop()


def test_runtime_heartbeat_stall_chaos_skips_beats(tmp_path):
    chaos.configure(Namespace(fault_inject="heartbeat-stall@0"))
    chaos.note_step(0)
    kv = _FakeKV()
    args = _runtime_args(tmp_path)
    args.elastic = False  # publisher only
    rt = elastic.HeartbeatRuntime(args, nproc=2, rank=0, client=kv)
    rt.start()
    try:
        time.sleep(0.3)
        assert rt._hb_key(0) not in kv.store  # every beat was skipped
        # a plain (unsupervised) run must not drop control-plane
        # bookkeeping files into the checkpoint directory
        assert elastic.read_state(str(tmp_path), 0) is None
    finally:
        rt.stop()


def test_runtime_self_stale_via_epoch_marker(tmp_path, monkeypatch):
    """Heartbeat keys are namespaced by the observer's OWN epoch, so a
    stale host can never see a newer incarnation's leases — the epoch
    existence marker is the cross-epoch signal that tells it THE RUN
    MOVED ON (fatal self-stale, not a false host-loss of every healthy
    survivor)."""
    kv = _FakeKV()
    kv.key_value_set(
        elastic.HeartbeatRuntime._epoch_marker_key(1), "1"
    )  # a newer incarnation already formed
    rt = elastic.HeartbeatRuntime(
        _runtime_args(tmp_path), nproc=2, rank=0, client=kv,
    ).start()
    try:
        deadline = time.monotonic() + 5.0
        while rt.verdict() is None and time.monotonic() < deadline:
            time.sleep(0.02)
        verdict = rt.verdict()
        assert verdict is not None and verdict.kind == "self-stale"
        assert "STALE epoch 0" in verdict.message
        assert isinstance(verdict.error(), guard.ConsistencyError)
        # no healthy peer was declared lost
        state = elastic.read_state(str(tmp_path), 0)
        assert state["survivors"] == [0, 1] and state["lost"] == {}
        # and every start published OUR epoch's marker for future stale
        # hosts to find
        assert elastic.HeartbeatRuntime._epoch_marker_key(0) in kv.store
    finally:
        rt.stop()


def test_reclassify_waits_only_for_peer_plausible_failures(
    tmp_path, monkeypatch
):
    """An ordinary Python bug must crash immediately (no heartbeat-budget
    stall); a collective failure waits for — and adopts — the verdict."""
    kv = _FakeKV()
    rt = elastic.HeartbeatRuntime(
        _runtime_args(tmp_path, interval=0.05, timeout=0.5),
        nproc=2, rank=0, client=kv,
    )
    monkeypatch.setattr(elastic, "_runtime", rt)
    # a plain bug: returns immediately with the original code
    t0 = time.monotonic()
    code = elastic.reclassify_with_verdict(
        ZeroDivisionError("bug"), elastic.EXIT_UNCAUGHT
    )
    assert code == elastic.EXIT_UNCAUGHT
    assert time.monotonic() - t0 < 0.5
    # a collective timeout with a verdict already recorded: adopted
    rt._verdict = elastic.Verdict("host-loss", [1], "rank 1 gone")
    code = elastic.reclassify_with_verdict(
        guard.CollectiveTimeoutError("stalled"),
        elastic.EXIT_COLLECTIVE_TIMEOUT,
    )
    assert code == elastic.EXIT_HOST_LOSS
    # an already-landed verdict reclassifies even a plain bug (no wait)
    code = elastic.reclassify_with_verdict(
        ZeroDivisionError("bug"), elastic.EXIT_UNCAUGHT
    )
    assert code == elastic.EXIT_HOST_LOSS


def test_runtime_real_partition_is_control_plane_even_with_one_peer(
    tmp_path
):
    """A REAL (non-chaos) service partition surfaces as the same deadline
    error an absent key does.  The monitor's own-epoch-marker probe is
    what tells them apart: a store that cannot produce a key that MUST
    exist is dark, so peer probes that round are not peer evidence — a
    2-host partition must end in a control-plane verdict (same-membership
    restart), never mutual host-loss verdicts (split brain)."""
    kv = _FakeKV()
    rt = elastic.HeartbeatRuntime(
        _runtime_args(tmp_path), nproc=2, rank=0, client=kv,
    ).start()
    try:
        # let the healthy plane form (marker written, peer publishing)
        kv.key_value_set(
            rt._hb_key(1),
            elastic.encode_lease(elastic.Lease(0, 1, 0, 0.0)),
        )
        time.sleep(0.2)
        assert rt.verdict() is None

        # partition: EVERY get now fails with the ambiguous deadline error
        def partitioned(key, timeout_ms):
            raise TimeoutError("Deadline Exceeded")

        kv.blocking_key_value_get = partitioned
        deadline = time.monotonic() + 8.0
        while rt.verdict() is None and time.monotonic() < deadline:
            time.sleep(0.02)
        verdict = rt.verdict()
        assert verdict is not None, "no verdict within the deadline"
        assert verdict.kind == "control-plane", verdict
        # the peer was NOT declared lost: survivors unchanged
        state = elastic.read_state(str(tmp_path), 0)
        assert state["survivors"] == [0, 1]
    finally:
        rt.stop()


def test_monitor_interval_floors_when_publishing_disabled(tmp_path):
    rt = elastic.HeartbeatRuntime(
        _runtime_args(tmp_path, interval=0.0, timeout=8.0),
        nproc=2, rank=0, client=None,
    )
    assert rt._monitor_interval() == 2.0  # timeout/4, never a hot poll
    rt2 = elastic.HeartbeatRuntime(
        _runtime_args(tmp_path, interval=0.25), nproc=2, rank=0, client=None,
    )
    assert rt2._monitor_interval() == 0.25


def test_collective_abort_hook_works_with_watchdog_disabled():
    """--collective-timeout 0 disables the WATCHDOG, not the elastic
    verdict abort: a collective wedged on a dead peer must still abandon
    within the heartbeat timeout."""
    guard.configure(Namespace(collective_timeout=0))
    boom = elastic.HostLossError("rank 1 lease expired")
    guard.set_collective_abort_check(lambda: boom)
    t0 = time.monotonic()
    with pytest.raises(elastic.HostLossError, match="lease expired"):
        guard.run_collective("all_gather_list", lambda: time.sleep(30))
    assert time.monotonic() - t0 < 10.0
    # with neither watchdog nor hook, the direct-call fast path remains
    guard.reset()
    guard.configure(Namespace(collective_timeout=0))
    assert guard.run_collective("all_reduce", lambda: 7) == 7


def test_runtime_single_process_is_inert(tmp_path):
    rt = elastic.HeartbeatRuntime(
        _runtime_args(tmp_path), nproc=1, rank=0, client=None,
    ).start()
    try:
        assert rt._threads == []
        # the membership view still lands for the supervisor
        assert elastic.read_state(str(tmp_path), 0)["world_size"] == 1
    finally:
        rt.stop()


def test_collective_abort_hook_preempts_watchdog_timeout():
    """A collective stalled on a peer the monitor has declared lost must
    abort within the heartbeat timeout (the hook), not the much longer
    --collective-timeout."""
    guard.configure(Namespace(collective_timeout=60.0))
    boom = elastic.HostLossError("rank 1 lease expired")
    guard.set_collective_abort_check(lambda: boom)
    t0 = time.monotonic()
    with pytest.raises(elastic.HostLossError, match="lease expired"):
        guard.run_collective("all_gather_list", lambda: time.sleep(30))
    assert time.monotonic() - t0 < 10.0  # nowhere near the 60s budget
    # the plane is poisoned exactly like a watchdog timeout
    with pytest.raises(guard.CollectiveTimeoutError, match="poisoned"):
        guard.run_collective("all_gather_list", lambda: 1)


# ---------------------------------------------------------------------------
# supervisor plumbing
# ---------------------------------------------------------------------------


def test_child_env_carries_membership_and_bumps_port(monkeypatch):
    monkeypatch.setenv("MASTER_PORT", "12000")
    env = elastic._child_env(epoch=2, restarts=1, rank=0, world=2,
                             base_port=12000)
    assert env[elastic.ENV_CHILD] == "1"
    assert env[elastic.ENV_EPOCH] == "2"
    assert env[elastic.ENV_RESTARTS] == "1"
    assert env["RANK"] == "0" and env["WORLD_SIZE"] == "2"
    assert env["MASTER_PORT"] == "12002"  # base + epoch: fresh rendezvous
    assert REPO in env["PYTHONPATH"].split(os.pathsep)
    assert env["UNICORE_TPU_RENDEZVOUS_TIMEOUT"] == str(
        elastic.RESTART_RENDEZVOUS_TIMEOUT_S
    )
    # slurm's env resolution outranks RANK/WORLD_SIZE in distributed_init,
    # so a re-formed membership must override it too
    monkeypatch.setenv("SLURM_PROCID", "2")
    monkeypatch.setenv("SLURM_NNODES", "3")
    env_s = elastic._child_env(epoch=1, restarts=1, rank=1, world=2,
                               base_port=None)
    assert env_s["SLURM_PROCID"] == "1" and env_s["SLURM_NNODES"] == "2"
    # a re-formed single-host run must NOT rendezvous at all
    env1 = elastic._child_env(epoch=2, restarts=1, rank=0, world=1,
                              base_port=12000)
    assert env1["WORLD_SIZE"] == "1" and env1["MASTER_PORT"] == "12000"


# ---------------------------------------------------------------------------
# end-to-end: the supervised CLI (single host, then a 2-process kill)
# ---------------------------------------------------------------------------

RUNNER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {repo!r})
sys.argv = ["train.py"] + {argv!r}
from unicore_tpu_cli.train import cli_main
cli_main()
"""

_JAX_CACHE = os.environ.get(
    "UNICORE_TPU_TEST_JAX_CACHE", "/tmp/unicore_tpu_test_jaxcache"
)
_SCALE = float(os.environ.get("UNICORE_TPU_TEST_TIMEOUT_SCALE", "0")) or (
    3.0 if (os.cpu_count() or 2) <= 1 else 1.0
)
CLI_TIMEOUT = int(600 * _SCALE)


def _cli_env(extra=None):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    if _JAX_CACHE != "0":
        env.setdefault("UNICORE_TPU_TEST_JAX_CACHE", _JAX_CACHE)
    env["JAX_COMPILATION_CACHE_DIR"] = _JAX_CACHE if _JAX_CACHE != "0" else ""
    env.update(extra or {})
    return env


def _run_cli(argv, expect_rc=0, env=None):
    proc = subprocess.run(
        [sys.executable, "-c", RUNNER.format(repo=REPO, argv=argv)],
        capture_output=True, text=True, timeout=CLI_TIMEOUT, cwd=REPO,
        env=_cli_env(env),
    )
    out = proc.stdout + proc.stderr
    if expect_rc is not None:
        assert proc.returncode == expect_rc, out[-6000:]
    return proc.returncode, out


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("bert_data")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "examples", "bert", "make_example_data.py"),
         str(d), "202", "40"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    return d


def _cli_args(data_dir, save_dir, max_update, extra=()):
    argv = [
        str(data_dir),
        "--task", "bert", "--loss", "masked_lm", "--arch", "bert_tiny",
        "--optimizer", "adam", "--lr-scheduler", "polynomial_decay",
        "--lr", "1e-3", "--warmup-updates", "2",
        "--total-num-update", str(max_update), "--max-update", str(max_update),
        "--max-epoch", "10", "--batch-size", "8", "--max-seq-len", "64",
        "--log-interval", "2", "--log-format", "simple",
        "--save-dir", os.path.join(save_dir, "ckpt"),
        "--tmp-save-dir", os.path.join(save_dir, "tmp"),
        "--num-workers", "0", "--seed", "1", "--no-progress-bar",
        "--required-batch-size-multiple", "1",
        "--save-interval-updates", "4", "--keep-interval-updates", "10",
        "--disable-validation",
    ]
    if _JAX_CACHE != "0":
        argv += ["--jax-compilation-cache-dir", _JAX_CACHE]
    return argv + list(extra)


SUPERVISOR_PROBE = r"""
import os, sys
sys.path.insert(0, {repo!r})
sys.argv = ["train.py"] + {argv!r}
from unicore_tpu.distributed import elastic

class FakeChild:
    # stands in for the trainer child: the probe is about the PARENT
    def __init__(self, cmd, env=None):
        assert cmd[1:3] == ["-m", "unicore_tpu_cli.train"], cmd
    def wait(self):
        return 0
    def poll(self):
        return 0

elastic.subprocess.Popen = FakeChild
from unicore_tpu_cli.train import cli_main
try:
    cli_main()
except SystemExit as e:
    assert e.code == 0, e.code
from jax._src import xla_bridge
print("SUPERVISOR_BACKENDS_INITIALIZED=%s" % xla_bridge.backends_are_initialized())
"""


def test_elastic_supervisor_never_initializes_a_backend(data_dir, tmp_path):
    """One process per chip: the --elastic parent parses options (which
    imports jax through the model registry) and supervises, but must
    never initialize a backend — on a TPU host that would take the chip
    from the trainer child it is about to start."""
    argv = _cli_args(data_dir, str(tmp_path), 4, extra=["--elastic"])
    proc = subprocess.run(
        [sys.executable, "-c",
         SUPERVISOR_PROBE.format(repo=REPO, argv=argv)],
        capture_output=True, text=True, timeout=CLI_TIMEOUT, cwd=REPO,
        env=_cli_env(),
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    assert "SUPERVISOR_BACKENDS_INITIALIZED=False" in out, out[-4000:]


def _load_model(path):
    from unicore_tpu import checkpoint_utils

    return checkpoint_utils.load_checkpoint_to_cpu(path)


@pytest.mark.slow
def test_cli_taxonomy_exit_code_corrupt_checkpoint_no_fallback(
    data_dir, tmp_path
):
    """The CLI must exit with the documented taxonomy code — not 1 — for a
    classified terminal error, so external supervisors can tell retryable
    from fatal without log-grepping.  A resume whose only checkpoint is
    torn, with no retained fallback, is the fatal corrupt-checkpoint case
    (exit 68)."""
    # run 1 stops at update 2: only checkpoint_last exists (the interval
    # cadence of 4 never fired), so there is nothing to fall back to
    _run_cli(_cli_args(data_dir, str(tmp_path), 2))
    last = tmp_path / "ckpt" / "checkpoint_last.pt"
    assert last.exists()
    with open(last, "r+b") as f:
        f.truncate(os.path.getsize(last) // 2)

    rc, out = _run_cli(_cli_args(data_dir, str(tmp_path), 4),
                       expect_rc=None)
    assert rc == elastic.EXIT_CORRUPT_CHECKPOINT, out[-4000:]
    assert "corrupt-checkpoint-no-fallback" in out
    assert "not retryable" in out


@pytest.mark.slow
def test_single_host_elastic_restart_replays_bit_identically(
    data_dir, tmp_path
):
    """Acceptance: a host-loss at update 6 under --elastic restarts from
    the verified update-4 checkpoint and replays updates 5..10 with NO
    update consumed twice and NONE skipped — proven by bit-identical
    final params against a manual crash-then-resume run of the same
    config (any double-consume or skip would shift the data stream and
    diverge the weights)."""
    # run A: supervised elastic run, killed at 6, auto-restarted
    a_dir = tmp_path / "a"
    rc, out_a = _run_cli(_cli_args(
        data_dir, str(a_dir), 10,
        extra=["--elastic", "--max-restarts", "2",
               "--restart-backoff", "0.2",
               "--fault-inject", "host-loss@6"],
    ))
    print(out_a[-3000:])  # surfaced for the CI smoke grep (pytest -s)
    assert "chaos: HOST LOSS" in out_a
    assert "ELASTIC RESTART 1/2" in out_a
    assert "DISARMED on restarted incarnation" in out_a
    assert "Loaded checkpoint" in out_a and "@ 4 updates" in out_a
    assert "num_updates: 10" in out_a
    assert "training completed cleanly" in out_a

    # run B: the same crash resumed MANUALLY (the operator workflow the
    # supervisor automates) — identical replay is the contract
    b_dir = tmp_path / "b"
    rc_b, out_b = _run_cli(
        _cli_args(data_dir, str(b_dir), 10,
                  extra=["--fault-inject", "raise@6"]),
        expect_rc=None,
    )
    assert rc_b != 0  # ChaosError is deliberately unclassified: stock crash
    _, out_b2 = _run_cli(_cli_args(data_dir, str(b_dir), 10))
    assert "num_updates: 10" in out_b2

    state_a = _load_model(str(a_dir / "ckpt" / "checkpoint_last.pt"))
    state_b = _load_model(str(b_dir / "ckpt" / "checkpoint_last.pt"))
    leaves_a = _flat(state_a["model"])
    leaves_b = _flat(state_b["model"])
    assert leaves_a.keys() == leaves_b.keys()
    for name in leaves_a:
        assert np.array_equal(leaves_a[name], leaves_b[name]), (
            f"param {name} diverged: the restart replayed different data"
        )
    # the elastic run's checkpoint records the incarnation that wrote it
    assert state_a["extra_state"]["membership_epoch"] == 1
    assert state_b["extra_state"]["membership_epoch"] == 0


def _flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        key = f"{prefix}/{k}"
        if isinstance(v, dict):
            out.update(_flat(v, key))
        else:
            out[key] = v
    return out


# -- 2-process host loss ----------------------------------------------------

_WORKER = r"""
import os, sys
rank = int(sys.argv[1]); port = sys.argv[2]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["MASTER_ADDR"] = "127.0.0.1"
os.environ["MASTER_PORT"] = port
os.environ["WORLD_SIZE"] = "2"
os.environ["RANK"] = str(rank)
sys.path.insert(0, {repo!r})
sys.argv = ["train.py"] + {argv_common!r} + (
    {argv_rank0!r} if rank == 0 else {argv_rank1!r}
)
from unicore_tpu_cli.train import cli_main
cli_main()
"""


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return str(s.getsockname()[1])


_HB_TIMEOUT = 4.0


def _run_two_proc_host_loss(data_dir, save_dir):
    common = _cli_args(
        data_dir, str(save_dir), 12,
        # --length-bucket 1 pads every batch to one fixed geometry so the
        # hosts' per-update shapes agree (shard mode) — the recommended
        # multi-host configuration; host-divergent raw lengths would fall
        # into gather slots every update
        extra=["--length-bucket", "1",
               "--heartbeat-interval", "0.5",
               "--heartbeat-timeout", str(_HB_TIMEOUT),
               "--collective-timeout", "120"],
    )
    rank0_extra = ["--elastic", "--max-restarts", "2",
                   "--restart-backoff", "0.3"]
    rank1_extra = ["--fault-inject", "host-loss@6@1"]
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, "-c",
             _WORKER.format(repo=REPO, argv_common=common,
                            argv_rank0=rank0_extra, argv_rank1=rank1_extra),
             str(r), port],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=REPO, env=_cli_env(),
        )
        for r in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=CLI_TIMEOUT)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out)
    return procs, outs


@pytest.mark.slow
def test_two_process_host_loss_detected_and_restarted(
    data_dir, tmp_path
):
    """Acceptance, end to end: rank 1 is hard-killed at update 6 of a
    2-process run.  Rank 0 (under --elastic) must (1) detect the silent
    peer within --heartbeat-timeout and record a verdict NAMING rank 1
    (in-process, or post-mortem from the persisted silence ages when
    jax's own coordination fatal aborts the child first), (2) bind the
    failure to the verdict instead of the 120s watchdog, (3) restart
    through its supervisor with the re-formed single-host membership,
    (4) resume from the verified update-4 checkpoint with the consumed-
    update cursor repartitioned over the new dp world size, and (5)
    finish training to --max-update 12."""
    for attempt in range(3):
        procs, (out0, out1) = _run_two_proc_host_loss(
            data_dir, tmp_path / f"try{attempt}"
        )
        if "gloo::EnforceNotMet" in out0 + out1 and (
            "chaos: HOST LOSS" not in out1
        ):
            # the documented pre-existing gloo CPU-rig flake (see PR 4
            # notes) killed a worker BEFORE the scenario's chaos kill
            # fired — that run proves nothing about the elastic plane
            print(f"attempt {attempt}: pre-existing gloo flake, retrying")
            continue
        break
    print(out0[-5000:])  # surfaced for the CI smoke step's grep (pytest -s)

    # rank 1 really died the hard way
    assert "chaos: HOST LOSS" in out1, out1[-3000:]
    assert procs[1].returncode == elastic.EXIT_WORKER_KILLED

    # (1) named-rank verdict (live or post-mortem), with the measured
    # silence bounded by the timeout plus polling granularity
    assert "ELASTIC HOST LOSS" in out0, out0[-6000:]
    assert "rank 1 heartbeat lease" in out0
    import re as _re

    m = _re.search(r"silent for ([0-9.]+)s", out0)
    assert m is not None
    assert float(m.group(1)) <= _HB_TIMEOUT + 3.0, m.group(0)
    post_mortem = "ELASTIC HOST LOSS (post-mortem)" in out0
    if not post_mortem:
        # (2) the failure was bound to the verdict, not the 120s
        # watchdog: the wedged collective was abandoned early, the racing
        # backend error was reclassified, or the agreed stop landed
        # cleanly and exited with the host-loss code
        assert (
            "abandoned at step" in out0
            or "reclassified as host-loss" in out0
            or "exiting 71" in out0
        ), out0[-6000:]
    # (3) the supervisor re-formed the membership without rank 1
    assert "re-forming membership WITHOUT rank 1" in out0
    assert "becomes rank 0/1" in out0
    assert "ELASTIC RESTART 1/2" in out0
    # (4) resume from the newest durable checkpoint (update 4; the kill at
    # 6 predates the update-8 save), repartitioned for the new world size
    assert "Loaded checkpoint" in out0 and "@ 4 updates" in out0
    assert "Iterator size changed" in out0  # dp world 2 -> 1 repartition
    # (5) the run finished
    assert "num_updates: 12" in out0
    assert "done training" in out0
    assert "training completed cleanly" in out0
    assert procs[0].returncode == 0
