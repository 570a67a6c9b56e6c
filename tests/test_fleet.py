"""Serving fleet: lease-registered replicas, the shedding router, and
rolling reload with a one-replica blast radius.

Unit layer (XLA-free): the file-backed fleet KV and its outcome
classification, replica-lease round-trips, service-confirmed membership
verdicts (incl. the outage-freezes-clocks rule), balance-by-estimate
power-of-two-choices, the retry budget and its two hard edges (different
replica only, never after the request body streamed), the drain/router
handshake (Retry-After, immediate readyz-flip removal), rolling-reload
halt ordering, and the replica-targeted chaos kinds.

Slow layer: a real 3-replica fleet (train → 3 × unicore-tpu-serve +
unicore-tpu-router) with ``replica-loss`` fired on replica 1 — the
router sheds around the death with zero post-window failures and the
merged trace names the verdict — plus a corrupt rolling reload that
halts after exactly one replica's RELOAD ROLLBACK.
"""

import json
import os
import shutil
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from types import SimpleNamespace

import numpy as np
import pytest

from unicore_tpu.checkpoint.emergency import Deadline
from unicore_tpu.distributed import chaos, elastic
from unicore_tpu.serve import request as rq
from unicore_tpu.serve.engine import ServeEngine
from unicore_tpu.serve.fleet import (
    FileKVClient,
    FleetView,
    ReplicaLease,
    ReplicaRegistrar,
    RollingReload,
    RouterEngine,
    open_fleet_kv,
)
from unicore_tpu.serve.fleet import registry as fleet_registry
from unicore_tpu.serve.fleet.router import (
    SHED_NO_REPLICA,
    SHED_RETRY_BUDGET,
    UPSTREAM_INCOMPLETE,
    UPSTREAM_TIMEOUT,
)
from unicore_tpu.serve.http import bind_server
from unicore_tpu.serve.reload import CheckpointWatcher
from unicore_tpu.utils import retry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.reset()
    yield
    chaos.reset()


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def fake_infer(service_s=0.0):
    def infer(variables, arr):
        if service_s:
            time.sleep(service_s)
        return np.asarray(arr).copy(), np.ones(
            arr.shape[0], dtype=np.float32
        )

    return infer


def publish_lease(client, name, address, *, seq, ready=True, est=0.0,
                  digest="d0", step=0, wall=None):
    """``wall`` is the lease's incarnation stamp: tests that compare
    incarnations pass strictly increasing stamps instead of resting on
    two clock reads differing after encoding."""
    client.key_value_set(
        fleet_registry.lease_key(name),
        ReplicaLease(
            name=name, address=address, ready=ready, digest=digest,
            est_delay_s=est,
            hb=elastic.Lease(
                epoch=0, seq=seq, step=step,
                wall=time.time() if wall is None else wall,
            ),
        ).encode(),
    )


class FakeReplica:
    """Scriptable replica HTTP plane: answers /v1/infer per ``mode`` and
    /v1/reload per ``reload_outcome``; counts hits."""

    def __init__(self, name="fr", mode="ok", reload_outcome="swapped",
                 stall_s=0.0):
        self.name = name
        self.mode = mode
        self.reload_outcome = reload_outcome
        self.stall_s = stall_s
        self.hits = 0
        self.reload_calls = 0
        fake = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _json(self, code, payload):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                if code == 503:
                    self.send_header("Retry-After", "1")
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                if self.path == "/v1/reload":
                    fake.reload_calls += 1
                    self._json(200, {"outcome": fake.reload_outcome})
                    return
                fake.hits += 1
                mode = fake.mode
                if fake.stall_s:
                    time.sleep(fake.stall_s)
                if mode == "ok":
                    doc = json.loads(body.decode() or "{}")
                    self._json(200, {
                        "id": doc.get("id", "?"), "status": "ok",
                        "output": [1], "replica": fake.name,
                        "deadline_ms": doc.get("deadline_ms"),
                    })
                elif isinstance(mode, tuple):  # ("status", code, payload)
                    self._json(mode[1], mode[2])
                elif mode == "drop-mid-body":
                    # status line + partial body, then a dead socket: the
                    # request REACHED the replica — never retryable
                    import socket as socket_mod

                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", "1000")
                    self.end_headers()
                    self.wfile.write(b'{"status": "ok", "output": [')
                    self.wfile.flush()
                    # shutdown (not close): FIN goes out NOW even though
                    # rfile/wfile still hold the fd
                    self.connection.shutdown(socket_mod.SHUT_RDWR)
                    self.close_connection = True

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.server.daemon_threads = True
        threading.Thread(
            target=self.server.serve_forever, daemon=True
        ).start()

    @property
    def address(self):
        return f"http://127.0.0.1:{self.server.server_address[1]}"

    def close(self):
        self.server.shutdown()


def make_view_and_router(tmp_path, replicas, **router_kw):
    """A FleetView over a real file KV populated with one lease per
    (name, address, est) triple, polled once so the balance set is
    live, plus a RouterEngine with a seeded rng."""
    import random

    client = open_fleet_kv(str(tmp_path / "fleetkv"))
    for i, (name, address, est) in enumerate(replicas):
        publish_lease(client, name, address, seq=1, est=est)
    view = FleetView(client, timeout=30.0)
    view.poll_once()
    router_kw.setdefault("rng", random.Random(7))
    return view, RouterEngine(view, **router_kw)


# ---------------------------------------------------------------------------
# fleet KV + lease round-trips
# ---------------------------------------------------------------------------


def test_file_kv_roundtrip_list_delete(tmp_path):
    client = open_fleet_kv(str(tmp_path / "kv"))
    client.key_value_set("a/b/k1", "v1")
    client.key_value_set("a/b/k2", "v2")
    assert client.blocking_key_value_get("a/b/k1", 50) == "v1"
    assert dict(client.key_value_dir_get("a/b")) == {
        "a/b/k1": "v1", "a/b/k2": "v2",
    }
    client.key_value_delete("a/b/k1")
    assert dict(client.key_value_dir_get("a/b")) == {"a/b/k2": "v2"}
    # deleting a missing key is a no-op, like the real client
    client.key_value_delete("a/b/k1")


def test_file_kv_outcomes_classify_like_the_coordination_client(tmp_path):
    """The PR-6 rule depends on the distinction: an ABSENT key is
    service-confirmed silence, an unreachable ROOT is a control-plane
    outage — retry.kv_fetch must classify both without special-casing
    the file backend."""
    root = tmp_path / "kv"
    client = open_fleet_kv(str(root))
    assert retry.kv_fetch(client, "nope/key", poll_ms=30) is retry.ABSENT
    client.key_value_set("yes/key", "v")
    assert retry.kv_fetch(client, "yes/key", poll_ms=30) == "v"
    shutil.rmtree(root)
    assert retry.kv_fetch(client, "yes/key", poll_ms=30) is retry.UNREACHABLE


def test_open_fleet_kv_rejects_unusable_root(tmp_path):
    from unicore_tpu.serve.fleet import FleetKVError

    f = tmp_path / "afile"
    f.write_text("x")
    with pytest.raises(FleetKVError):
        open_fleet_kv(str(f), create=False)


def test_replica_lease_roundtrip():
    lease = ReplicaLease(
        name="r1", address="http://10.0.0.7:8693", ready=True,
        digest="abc123", est_delay_s=0.25,
        hb=elastic.Lease(epoch=0, seq=12, step=340, wall=1754300000.0),
    )
    back = fleet_registry.decode_replica_lease(lease.encode())
    assert back.name == "r1" and back.address == "http://10.0.0.7:8693"
    assert back.ready and back.digest == "abc123"
    assert back.est_delay_s == pytest.approx(0.25)
    assert back.hb.seq == 12 and back.hb.step == 340
    with pytest.raises(ValueError):
        fleet_registry.decode_replica_lease('{"tag": "wrong"}')
    with pytest.raises(ValueError):
        fleet_registry.check_name("bad name/../x")


def test_registrar_publishes_readiness_and_says_goodbye(tmp_path):
    client = open_fleet_kv(str(tmp_path / "kv"))
    ready = [False]
    reg = ReplicaRegistrar(
        client, "r0", "http://127.0.0.1:9", interval_s=30.0,
        ready_fn=lambda: ready[0], est_delay_fn=lambda: 0.5,
        digest_fn=lambda: "dg", served_fn=lambda: 7,
    ).start()
    try:
        raw = client.blocking_key_value_get(
            fleet_registry.lease_key("r0"), 100
        )
        lease = fleet_registry.decode_replica_lease(raw)
        assert not lease.ready and lease.digest == "dg"
        assert lease.est_delay_s == 0.5 and lease.hb.step == 7
        seq0 = lease.hb.seq
        ready[0] = True
        reg.publish_now()  # the drain/readiness handshake beat
        lease = fleet_registry.decode_replica_lease(
            client.blocking_key_value_get(
                fleet_registry.lease_key("r0"), 100
            )
        )
        assert lease.ready and lease.hb.seq > seq0
    finally:
        reg.stop(goodbye=True)
    # goodbye DELETED the key: the router deregisters, no loss verdict
    assert retry.kv_fetch(
        client, fleet_registry.lease_key("r0"), poll_ms=30
    ) is retry.ABSENT


def test_model_digest_tracks_content():
    tree = {"params": {"w": np.zeros((2, 2)), "b": np.ones(3)}}
    same = {"params": {"w": np.zeros((2, 2)), "b": np.ones(3)}}
    other = {"params": {"w": np.zeros((2, 2)), "b": np.full(3, 2.0)}}
    assert fleet_registry.model_digest(tree) == \
        fleet_registry.model_digest(same)
    assert fleet_registry.model_digest(tree) != \
        fleet_registry.model_digest(other)


# ---------------------------------------------------------------------------
# membership: verdicts, deregistration, the outage freeze
# ---------------------------------------------------------------------------


def _stepped_view(tmp_path, timeout=5.0):
    client = open_fleet_kv(str(tmp_path / "kv"))
    now = [0.0]
    view = FleetView(client, timeout=timeout, clock=lambda: now[0])
    return client, view, now


def test_membership_names_the_silent_replica(tmp_path, caplog):
    """A lease the store answers about but that stops advancing ripens
    into a verdict NAMING the replica; the advancing peer stays."""
    client, view, now = _stepped_view(tmp_path)
    publish_lease(client, "r0", "http://h:1", seq=1)
    publish_lease(client, "r1", "http://h:2", seq=1)
    view.poll_once(0.0)
    assert {r.name for r in view.balance_set()} == {"r0", "r1"}
    # r0 keeps beating, r1 goes silent (the key stays — os._exit leaves
    # it rotting in the store, exactly the replica-loss chaos shape)
    for t in (2.0, 4.0, 6.5):
        publish_lease(client, "r0", "http://h:1", seq=int(t * 10))
        now[0] = t
        with caplog.at_level("ERROR"):
            view.poll_once(t)
    assert {r.name for r in view.balance_set()} == {"r0"}
    assert "r1" in view.stats()["lost"]
    joined = " ".join(caplog.messages)
    assert "FLEET REPLICA-LOSS" in joined and "replica r1" in joined
    # the corpse's last lease on disk does NOT resurrect it next round
    now[0] = 7.0
    view.poll_once(7.0)
    assert {r.name for r in view.balance_set()} == {"r0"}
    # ...but a genuinely restarted replica (advancing seq) rejoins
    publish_lease(client, "r1", "http://h:2", seq=100)
    view.poll_once(7.5)
    assert {r.name for r in view.balance_set()} == {"r0", "r1"}


def test_membership_restarted_replica_rejoins_despite_fresh_seq(tmp_path):
    """Regression: a replica restarted under the SAME NAME after a loss
    verdict re-counts seq from 1 — the corpse guard must key on the
    incarnation (seq AND wall stamp), or the healthy restart would stay
    invisible until it out-counted the dead incarnation's whole life."""
    client, view, now = _stepped_view(tmp_path)
    # long-lived incarnation: seq climbed high before the death
    publish_lease(client, "r0", "http://h:1", seq=1800, wall=1000.0)
    view.poll_once(0.0)
    for t in (3.0, 6.5):
        now[0] = t
        view.poll_once(t)
    assert "r0" in view.stats()["lost"]
    # restart: fresh registrar, seq 1, but a NEW (strictly later) wall stamp
    publish_lease(client, "r0", "http://h:1", seq=1, wall=1007.0)
    now[0] = 7.0
    view.poll_once(7.0)
    assert [r.name for r in view.balance_set()] == ["r0"]
    assert view.stats()["lost"] == []
    assert view.stats()["losses"] == 1  # the monotone counter stands


def test_membership_ignores_unroutable_advertised_address(tmp_path,
                                                          caplog):
    """A lease advertising a port-less address must never enter the
    balance set — every leg to it would be an unshedable router error."""
    client, view, now = _stepped_view(tmp_path)
    publish_lease(client, "bad", "http://10.0.0.7", seq=1)
    publish_lease(client, "good", "http://10.0.0.7:8693", seq=1)
    with caplog.at_level("ERROR"):
        view.poll_once(0.0)
    assert [r.name for r in view.balance_set()] == ["good"]
    assert "FLEET BAD-ADDRESS" in " ".join(caplog.messages)


def test_membership_outage_freezes_verdicts_not_mints_them(tmp_path,
                                                           caplog):
    """PR 6's rule on the fleet tier: while the store is unreachable no
    replica-loss verdict can be minted, no matter how long the outage
    outlives the lease timeout — and a replica that kept publishing
    through the outage is still a member when the store returns."""
    client, view, now = _stepped_view(tmp_path, timeout=5.0)
    publish_lease(client, "r0", "http://h:1", seq=1)
    view.poll_once(0.0)
    assert len(view.balance_set()) == 1
    # the store goes dark for 4x the lease timeout
    dark = client.root + ".dark"
    os.rename(client.root, dark)
    with caplog.at_level("WARNING"):
        for t in (2.0, 8.0, 14.0, 20.0):
            now[0] = t
            view.poll_once(t)
    assert view.frozen_since is not None
    assert view.stats()["frozen"] is True
    # no verdict minted: the replica is still routable on the last
    # confirmed view, and nothing landed in lost
    assert len(view.balance_set()) == 1
    assert view.stats()["lost"] == []
    assert "FLEET FREEZE" in " ".join(caplog.messages)
    # the store returns; the replica kept publishing all along (chaos
    # kv-outage gates only the READ side) — silence never accrued
    os.rename(dark, client.root)
    publish_lease(client, "r0", "http://h:1", seq=50)
    now[0] = 21.0
    view.poll_once(21.0)
    assert view.frozen_since is None
    assert len(view.balance_set()) == 1
    assert view.stats()["lost"] == []


def test_membership_empty_fleet_is_not_an_outage(tmp_path):
    """A healthy store with no replicas yet must not trip the freeze:
    the listing IS a service answer."""
    client, view, now = _stepped_view(tmp_path, timeout=2.0)
    for t in (0.0, 3.0, 6.0):
        now[0] = t
        view.poll_once(t)
    assert view.frozen_since is None
    assert view.balance_set() == []


def test_membership_deregisters_on_deleted_key(tmp_path):
    """A clean drain deletes its lease (the registrar's goodbye): the
    next service-confirmed listing removes the replica WITHOUT a loss
    verdict."""
    client, view, now = _stepped_view(tmp_path)
    publish_lease(client, "r0", "http://h:1", seq=1)
    view.poll_once(0.0)
    assert len(view.balance_set()) == 1
    client.key_value_delete(fleet_registry.lease_key("r0"))
    now[0] = 1.0
    view.poll_once(1.0)
    assert view.balance_set() == []
    assert view.stats()["lost"] == []  # deregistered, not lost


def test_down_mark_clears_only_on_fresh_ready_lease(tmp_path):
    client, view, now = _stepped_view(tmp_path)
    publish_lease(client, "r0", "http://h:1", seq=3)
    view.poll_once(0.0)
    view.mark_unready("r0", "503:draining")
    assert view.balance_set() == []
    # the SAME lease (seq 3) re-observed does not resurrect it
    now[0] = 1.0
    view.poll_once(1.0)
    assert view.balance_set() == []
    # a stale not-ready beat doesn't either
    publish_lease(client, "r0", "http://h:1", seq=4, ready=False)
    now[0] = 2.0
    view.poll_once(2.0)
    assert view.balance_set() == []
    # a FRESH ready beat past the mark re-admits
    publish_lease(client, "r0", "http://h:1", seq=5, ready=True)
    now[0] = 3.0
    view.poll_once(3.0)
    assert [r.name for r in view.balance_set()] == ["r0"]


# ---------------------------------------------------------------------------
# routing: balance by estimate, retry budget, the two hard edges
# ---------------------------------------------------------------------------


def test_balance_by_estimate_power_of_two(tmp_path):
    fast = FakeReplica("fast")
    slow = FakeReplica("slow")
    try:
        view, router = make_view_and_router(
            tmp_path,
            [("fast", fast.address, 0.01), ("slow", slow.address, 2.0)],
        )
        for _ in range(10):
            code, body = router.handle_infer(
                {"tokens": [1, 2]}, Deadline(5.0)
            )
            assert code == 200 and body["replica"] == "fast"
        # with two replicas p2c always compares both: every request
        # lands on the lower published estimate
        assert fast.hits == 10 and slow.hits == 0
        assert router.stats()["by_replica"] == {"fast": 10}
    finally:
        fast.close()
        slow.close()


def test_p2c_spreads_under_equal_estimates(tmp_path):
    """Tied (or stale-identical) admission estimates must NOT collapse
    p2c onto one replica (the PR-13 bench regression: by_replica
    {"b0": 285} at n=2): equal scores are a jittered coin flip, so both
    replicas carry a meaningful share."""
    view, router = make_view_and_router(
        tmp_path,
        [("b0", "http://127.0.0.1:1", 0.0),
         ("b1", "http://127.0.0.1:2", 0.0)],
    )
    counts = {"b0": 0, "b1": 0}
    for _ in range(300):
        counts[router.pick_replica().name] += 1
    assert min(counts.values()) >= 90, counts


def test_p2c_inflight_cost_breaks_stale_strict_order(tmp_path):
    """A slightly-lower STALE estimate must not win every pick: under
    load the router's own fresh in-flight count costs the favored
    replica forward until the pair spreads (the estimate itself only
    refreshes at the next lease round, which never comes here)."""
    view, router = make_view_and_router(
        tmp_path,
        [("b0", "http://127.0.0.1:1", 0.010),
         ("b1", "http://127.0.0.1:2", 0.012)],
    )
    counts = {"b0": 0, "b1": 0}
    # concurrent-load shape: dispatches outstanding, none completing
    for _ in range(40):
        pick = router.pick_replica()
        counts[pick.name] += 1
        view.note_dispatch(pick.name)
    # b0 wins the first pick; its growing in-flight cost then pushes its
    # score past b1's and the stream alternates
    assert counts["b0"] >= 1 and counts["b1"] >= 15, counts


def test_p2c_three_replicas_no_starvation_under_load(tmp_path):
    """n=3 regression shape (bench showed zero traffic to one replica):
    with equal estimates and live inflight accounting every replica gets
    a share."""
    view, router = make_view_and_router(
        tmp_path,
        [(f"b{i}", f"http://127.0.0.1:{i + 1}", 0.0) for i in range(3)],
    )
    counts = {f"b{i}": 0 for i in range(3)}
    for _ in range(300):
        pick = router.pick_replica()
        counts[pick.name] += 1
        view.note_dispatch(pick.name)
        view.note_done(pick.name)
    assert min(counts.values()) >= 50, counts


def test_router_rewrites_deadline_to_remaining_budget(tmp_path):
    r = FakeReplica("r0")
    try:
        view, router = make_view_and_router(
            tmp_path, [("r0", r.address, 0.0)]
        )
        deadline = Deadline(10.0)
        time.sleep(0.15)
        code, body = router.handle_infer({"tokens": [1]}, deadline)
        assert code == 200
        # downstream sees what is LEFT, not the client's original number
        assert body["deadline_ms"] < 10000.0 - 100.0
    finally:
        r.close()


def test_retry_connect_failure_reroutes_to_different_replica(tmp_path):
    alive = FakeReplica("alive")
    try:
        # dead: a bound-then-closed port — connect refused, nothing
        # streamed, the one clearly-retryable failure
        import socket as socket_mod

        s = socket_mod.socket()
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
        s.close()
        view, router = make_view_and_router(
            tmp_path,
            [("dead", f"http://127.0.0.1:{dead_port}", 0.0),
             ("alive", alive.address, 5.0)],  # dead scores better
        )
        code, body = router.handle_infer({"tokens": [1]}, Deadline(5.0))
        assert code == 200 and body["replica"] == "alive"
        assert router.retries == 1
        # the dead replica was down-marked immediately: the next request
        # never dials it
        assert view.get("dead").down is not None
        code, body = router.handle_infer({"tokens": [1]}, Deadline(5.0))
        assert code == 200 and router.retries == 1
    finally:
        alive.close()


def test_retry_budget_exhausts_with_named_shed(tmp_path):
    import socket as socket_mod

    ports = []
    for _ in range(4):
        s = socket_mod.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        s.close()
    view, router = make_view_and_router(
        tmp_path,
        [(f"d{i}", f"http://127.0.0.1:{p}", 0.0)
         for i, p in enumerate(ports)],
        retry_budget=1,
    )
    code, body = router.handle_infer({"tokens": [1]}, Deadline(5.0))
    assert code == 503
    assert body["reason"] == SHED_RETRY_BUDGET
    assert len(body["replicas_tried"]) == 2  # 1 try + 1 retry, distinct
    assert len(set(body["replicas_tried"])) == 2
    assert router.shed_counts[SHED_RETRY_BUDGET] == 1


def test_no_retry_after_body_streamed(tmp_path):
    """The hard edge: a replica that died MID-RESPONSE may have executed
    the request — the router answers a named 502 and never recomputes it
    on another replica."""
    dropper = FakeReplica("dropper", mode="drop-mid-body")
    backup = FakeReplica("backup")
    try:
        view, router = make_view_and_router(
            tmp_path,
            [("dropper", dropper.address, 0.0),
             ("backup", backup.address, 5.0)],  # dropper scores better
        )
        code, body = router.handle_infer({"tokens": [1]}, Deadline(5.0))
        assert code == 502
        assert UPSTREAM_INCOMPLETE in body["reason"]
        assert backup.hits == 0  # NEVER retried elsewhere
        assert router.retries == 0
    finally:
        dropper.close()
        backup.close()


def test_deadline_bounds_the_proxy_leg_and_down_marks(tmp_path):
    """chaos replica-stall's router half: a live-but-dark replica costs
    one request its deadline (504, bounded), gets down-marked, and the
    fleet sheds around it — lease health alone never catches this."""
    zombie = FakeReplica("zombie", stall_s=8.0)
    alive = FakeReplica("alive")
    try:
        view, router = make_view_and_router(
            tmp_path,
            [("zombie", zombie.address, 0.0),
             ("alive", alive.address, 5.0)],
        )
        t0 = time.monotonic()
        code, body = router.handle_infer({"tokens": [1]}, Deadline(0.6))
        elapsed = time.monotonic() - t0
        assert code == 504 and body["reason"] == UPSTREAM_TIMEOUT
        assert elapsed < 4.0  # bounded by the deadline, not the stall
        assert view.get("zombie").down is not None
        # the fleet sheds AROUND the zombie from now on
        code, body = router.handle_infer({"tokens": [1]}, Deadline(5.0))
        assert code == 200 and body["replica"] == "alive"
    finally:
        zombie.close()
        alive.close()


def test_replica_503_is_immediate_removal_and_safe_retry(tmp_path):
    """The drain/router handshake: one 503 (the /readyz flip made
    concrete) removes the replica from the balance set NOW — not at the
    next lease round — and the request re-routes (a complete 503 is a
    definitive 'not me', safe to retry)."""
    draining = FakeReplica(
        "draining",
        mode=("status", 503, {"status": "shed", "reason": "draining"}),
    )
    alive = FakeReplica("alive")
    try:
        view, router = make_view_and_router(
            tmp_path,
            [("draining", draining.address, 0.0),
             ("alive", alive.address, 5.0)],
        )
        code, body = router.handle_infer({"tokens": [1]}, Deadline(5.0))
        assert code == 200 and body["replica"] == "alive"
        assert draining.hits == 1 and router.retries == 1
        info = view.get("draining")
        assert info.down is not None and "draining" in info.down[0]
        # immediately out of the balance set: the next request never
        # touches it (no second 503 round-trip)
        code, body = router.handle_infer({"tokens": [1]}, Deadline(5.0))
        assert code == 200 and draining.hits == 1
    finally:
        draining.close()
        alive.close()


def test_empty_balance_set_sheds_no_replica(tmp_path):
    client = open_fleet_kv(str(tmp_path / "kv"))
    view = FleetView(client, timeout=30.0)
    router = RouterEngine(view)
    code, body = router.handle_infer({"tokens": [1]}, Deadline(1.0))
    assert code == 503 and body["reason"] == SHED_NO_REPLICA
    assert router.shed_counts[SHED_NO_REPLICA] == 1


def test_sigterm_style_drain_loses_zero_new_requests(tmp_path):
    """Regression for the drain handshake end-to-end over REAL replica
    transports: replica A starts draining mid-traffic (readyz flips, its
    503s carry Retry-After) — every non-in-flight request the router
    accepts afterwards still succeeds, via B."""
    engines, servers = [], []
    for _ in range(2):
        eng = ServeEngine(
            {"params": {"w": np.zeros((2, 2))}}, fake_infer(),
            bucket_edges=(16,), batch_size=2, pad_idx=1,
            admission_capacity=64,
        )
        eng.warmup()
        eng.start()
        srv = bind_server("127.0.0.1", 0, eng, read_timeout_s=2.0)
        srv.start()
        engines.append(eng)
        servers.append(srv)
    try:
        addr = [
            f"http://127.0.0.1:{s.server_address[1]}" for s in servers
        ]
        view, router = make_view_and_router(
            tmp_path, [("a", addr[0], 0.0), ("b", addr[1], 0.0)]
        )
        # replica A's 503s really carry Retry-After (satellite contract)
        engines[0].queue.begin_drain()
        engines[0].set_ready(False, "draining")
        req = urllib.request.Request(
            addr[0] + "/v1/infer",
            data=json.dumps({"tokens": [1]}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        try:
            urllib.request.urlopen(req, timeout=5)
            raise AssertionError("draining replica must 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert e.headers.get("Retry-After") is not None
        # zero lost requests at the router: everything routes via B
        for _ in range(20):
            code, body = router.handle_infer(
                {"tokens": [2, 3]}, Deadline(10.0)
            )
            assert code == 200
        codes = router.stats()["by_code"]
        assert set(codes) == {"200"} and codes["200"] == 20
    finally:
        for eng in engines:
            eng.stop()
        for srv in servers:
            srv.shutdown()


# ---------------------------------------------------------------------------
# rolling reload: one at a time, halt on first rollback
# ---------------------------------------------------------------------------


def _view_over(tmp_path, fakes):
    view, _ = make_view_and_router(
        tmp_path, [(f.name, f.address, 0.0) for f in fakes]
    )
    return view


def test_rolling_reload_swaps_all_when_healthy(tmp_path):
    fakes = [FakeReplica(f"r{i}") for i in range(3)]
    try:
        view = _view_over(tmp_path, fakes)
        roll = RollingReload(
            CheckpointWatcher(str(tmp_path / "ckpt.pt")), view,
            interval_s=1.0,
        )
        history = roll.roll("/fake/candidate.pt")
        assert history == [(f"r{i}", "swapped") for i in range(3)]
        assert roll.rolled == 1 and roll.halted == 0
        assert all(f.reload_calls == 1 for f in fakes)
    finally:
        for f in fakes:
            f.close()


def test_rolling_reload_halts_on_first_rollback(tmp_path, caplog):
    """The blast-radius guarantee: replica r1 rolls back → the roll
    HALTS, r2 is NEVER asked, and the fleet keeps serving the old
    snapshot (r1 included — its own rollback restored it)."""
    fakes = [
        FakeReplica("r0", reload_outcome="swapped"),
        FakeReplica("r1", reload_outcome="rejected:verify"),
        FakeReplica("r2", reload_outcome="swapped"),
    ]
    try:
        view = _view_over(tmp_path, fakes)
        roll = RollingReload(
            CheckpointWatcher(str(tmp_path / "ckpt.pt")), view,
            interval_s=1.0,
        )
        with caplog.at_level("ERROR"):
            history = roll.roll("/fake/candidate.pt")
        assert history == [("r0", "swapped"), ("r1", "rejected:verify")]
        assert roll.halted == 1 and roll.rolled == 0
        assert fakes[2].reload_calls == 0  # never asked
        joined = " ".join(caplog.messages)
        assert "ROLLING RELOAD HALT" in joined and "r1" in joined
        # every replica is back in (or never left) the balance set
        assert len(view.balance_set()) == 3
    finally:
        for f in fakes:
            f.close()


def test_rolling_reload_unreachable_replica_halts_too(tmp_path):
    """A replica that cannot even be ASKED halts the roll exactly like a
    rollback — pressing on would widen the blast radius blindly."""
    import socket as socket_mod

    s = socket_mod.socket()
    s.bind(("127.0.0.1", 0))
    dead_port = s.getsockname()[1]
    s.close()
    alive = FakeReplica("r1")
    try:
        view, _ = make_view_and_router(
            tmp_path,
            [("r0", f"http://127.0.0.1:{dead_port}", 0.0),
             ("r1", alive.address, 0.0)],
        )
        roll = RollingReload(
            CheckpointWatcher(str(tmp_path / "ckpt.pt")), view,
            interval_s=1.0, reload_timeout_s=2.0,
        )
        history = roll.roll("/fake/candidate.pt")
        assert len(history) == 1 and history[0][0] == "r0"
        assert history[0][1].startswith("unreachable")
        assert roll.halted == 1
        assert alive.reload_calls == 0
    finally:
        alive.close()


def test_serve_http_reload_endpoint(tmp_path):
    """POST /v1/reload runs the replica's OWN verify→probe→swap and
    answers the named outcome; non-fleet replicas 404 it."""
    eng = ServeEngine(
        {"params": {"w": np.zeros((2, 2))}}, fake_infer(),
        bucket_edges=(16,), batch_size=2, pad_idx=1,
    )
    eng.warmup()
    outcomes = ["swapped"]

    class FakeReloader:
        def consider(self, path):
            return outcomes[0]

    srv = bind_server(
        "127.0.0.1", 0, eng, read_timeout_s=2.0,
        reloader=FakeReloader(), reload_path="/served/ckpt.pt",
    )
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        req = urllib.request.Request(
            base + "/v1/reload",
            data=json.dumps({"path": "ignored"}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=5) as resp:
            assert json.loads(resp.read())["outcome"] == "swapped"
    finally:
        eng.stop()
        srv.shutdown()
    # a replica started WITHOUT --advertise is not fleet-reloadable
    eng2 = ServeEngine(
        {"params": {"w": np.zeros((2, 2))}}, fake_infer(),
        bucket_edges=(16,), batch_size=2, pad_idx=1,
    )
    eng2.warmup()
    srv2 = bind_server("127.0.0.1", 0, eng2, read_timeout_s=2.0)
    srv2.start()
    try:
        base = f"http://127.0.0.1:{srv2.server_address[1]}"
        req = urllib.request.Request(
            base + "/v1/reload", data=b"{}",
            headers={"Content-Type": "application/json"}, method="POST",
        )
        try:
            urllib.request.urlopen(req, timeout=5)
            raise AssertionError("expected 404")
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        eng2.stop()
        srv2.shutdown()


# ---------------------------------------------------------------------------
# chaos: replica-loss / replica-stall
# ---------------------------------------------------------------------------


def _arm(spec):
    chaos.configure(SimpleNamespace(fault_inject=spec))


def test_replica_chaos_specs_parse_with_idx_targeting():
    plan = chaos.parse_fault_spec("replica-loss@3@1")
    assert plan.kind == "replica-loss" and plan.step == 3
    assert plan._rank == 1
    plan = chaos.parse_fault_spec("replica-stall:2.5@0")
    assert plan.kind == "replica-stall" and plan.param == 2.5
    assert "replica" in repr(plan)
    # the single-process serve kinds still reject targeting
    with pytest.raises(ValueError, match="serving plane"):
        chaos.parse_fault_spec("request-flood@0@1")


def test_replica_loss_fires_on_matching_index_only(monkeypatch):
    exits = []
    monkeypatch.setattr(os, "_exit", lambda code: exits.append(code))
    _arm("replica-loss@2@1")
    chaos.set_replica_index(0)
    chaos.note_serve_batch(5)
    assert exits == []  # wrong replica
    chaos.set_replica_index(1)
    chaos.note_serve_batch(1)
    assert exits == []  # before the trigger batch
    chaos.note_serve_batch(2)
    assert exits == [chaos.HOST_LOSS_EXIT_CODE]
    # one-shot: the (hypothetically surviving) process never refires
    chaos.note_serve_batch(3)
    assert exits == [chaos.HOST_LOSS_EXIT_CODE]


def test_replica_stall_window_and_targeting():
    _arm("replica-stall:0.3@0@2")
    chaos.set_replica_index(0)
    chaos.note_serve_batch(0)
    assert not chaos.replica_stall_active()  # targeted at replica 2
    chaos.reset()
    _arm("replica-stall:0.3@0@0")
    chaos.set_replica_index(0)
    chaos.note_serve_batch(0)
    assert chaos.replica_stall_active()
    time.sleep(0.4)
    assert not chaos.replica_stall_active()  # window closed


# ---------------------------------------------------------------------------
# exit codes, prometheus, trace
# ---------------------------------------------------------------------------


def test_router_exit_codes_extend_the_taxonomy():
    from unicore_tpu_cli import router as router_cli
    from unicore_tpu_cli import serve as serve_cli

    assert router_cli.EXIT_ROUTER_BIND == serve_cli.EXIT_SERVE_BIND == 75
    assert router_cli.EXIT_ROUTER_FLEET_KV == \
        serve_cli.EXIT_SERVE_FLEET_KV == 78
    # no collision with the training taxonomy (65-74)
    assert 78 not in elastic.EXIT_CODE_NAMES
    assert 78 in router_cli.ROUTER_EXIT_CODE_NAMES
    assert 78 in serve_cli.SERVE_EXIT_CODE_NAMES


def test_prometheus_render_router(tmp_path):
    r = FakeReplica("r0")
    try:
        view, router = make_view_and_router(
            tmp_path, [("r0", r.address, 0.25)]
        )
        assert router.handle_infer({"tokens": [1]}, Deadline(5.0))[0] == 200
        from unicore_tpu.telemetry import prometheus as prom

        text = prom.render_router(router)
        assert "unicore_tpu_router_ready 1" in text
        assert "unicore_tpu_router_ok_total 1" in text
        assert 'unicore_tpu_router_replica_proxied_total{replica="r0"} 1' \
            in text
        assert "unicore_tpu_router_replicas_routable 1" in text
    finally:
        r.close()


def test_trace_summarizes_fleet_post_mortem():
    """The router's anchorless stream merges into a post-mortem that
    names which replica died, when the router noticed, and what got shed
    in the gap — plus how far a rolling reload got before halting."""
    from unicore_tpu.telemetry import trace

    base = {"run_id": "t", "attempt": 0, "rank": 0,
            "membership_epoch": 0, "update": -1, "mono": 0.0}
    records = [
        {**base, "wall": 100.0, "kind": "router-start"},
        {**base, "wall": 106.5, "kind": "fleet-verdict",
         "verdict": "replica-loss", "replica": "r1",
         "message": "heartbeat lease silent for 5.2s"},
        {**base, "wall": 104.0, "kind": "router-retry",
         "reason": "connect-failure (refused)", "replica": "r1"},
        {**base, "wall": 104.5, "kind": "router-shed",
         "reason": "retry-budget-exhausted", "count": 2, "code": 503},
        {**base, "wall": 110.0, "kind": "fleet-reload", "event": "halt",
         "replica": "r0", "outcome": "rejected:verify",
         "never_asked": 2, "path": "/c.pt"},
    ]
    merged = trace.merge(records)
    lines = "\n".join(trace.summarize(merged))
    assert "replica r1 REPLICA-LOSS noticed by the router at +6.500s" \
        in lines
    assert "heartbeat lease silent" in lines
    assert "router retries" in lines and "connect-failure" in lines
    assert "router sheds" in lines and "retry-budget-exhausted x2" in lines
    assert "ROLLING RELOAD HALTED" in lines and "r0" in lines
    assert "2 replica(s) never asked" in lines


# ---------------------------------------------------------------------------
# CLI e2e (slow): a real 3-replica fleet under chaos
# ---------------------------------------------------------------------------

_SCALE = float(os.environ.get("UNICORE_TPU_TEST_TIMEOUT_SCALE", "0")) or (
    3.0 if (os.cpu_count() or 2) <= 1 else 1.0
)
CLI_TIMEOUT = int(600 * _SCALE)
_JAX_CACHE = os.environ.get(
    "UNICORE_TPU_TEST_JAX_CACHE", "/tmp/unicore_tpu_e2e_jaxcache"
)

_RUNNER = r"""
import os, sys
os.environ["UNICORE_TPU_PLATFORM"] = "cpu"
os.environ["UNICORE_TPU_CPU_DEVICES"] = "1"
sys.path.insert(0, {repo!r})
sys.argv = [{prog!r}] + {argv!r}
from unicore_tpu_cli.{module} import cli_main
cli_main()
"""


def _runner_cmd(module, argv):
    return [
        sys.executable, "-c",
        _RUNNER.format(repo=REPO, prog=module, argv=argv, module=module),
    ]


@pytest.fixture(scope="module")
def fleet_checkpoint(tmp_path_factory):
    """Train 2 updates of bert_tiny; the checkpoint every replica serves."""
    root = tmp_path_factory.mktemp("fleet_e2e")
    data = root / "data"
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "examples", "bert", "make_example_data.py"),
         str(data), "64", "40"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    argv = [
        str(data),
        "--task", "bert", "--loss", "masked_lm", "--arch", "bert_tiny",
        "--optimizer", "adam", "--lr-scheduler", "polynomial_decay",
        "--lr", "1e-3", "--warmup-updates", "1",
        "--total-num-update", "2", "--max-update", "2",
        "--max-epoch", "10", "--batch-size", "4", "--max-seq-len", "64",
        "--log-interval", "1", "--log-format", "simple",
        "--save-dir", str(root / "ckpt"),
        "--tmp-save-dir", str(root / "tmp"),
        "--num-workers", "0", "--seed", "1", "--no-progress-bar",
        "--disable-validation", "--required-batch-size-multiple", "1",
        "--jax-compilation-cache-dir", _JAX_CACHE,
    ]
    proc = subprocess.run(
        _runner_cmd("train", argv), capture_output=True, text=True,
        timeout=CLI_TIMEOUT, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    ckpt = root / "ckpt" / "checkpoint_last.pt"
    assert ckpt.exists()
    return ckpt


class Proc:
    """A CLI subprocess with log capture + line discovery."""

    def __init__(self, tmp_path, module, tag, argv):
        self.log_path = tmp_path / f"{tag}.log"
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            _runner_cmd(module, argv),
            stdout=self._log, stderr=subprocess.STDOUT, cwd=REPO,
        )
        self.base = None

    def log(self):
        with open(self.log_path) as f:
            return f.read()

    def wait_for(self, needle, budget, alive_required=True):
        deadline = time.monotonic() + budget
        while time.monotonic() < deadline:
            if needle in self.log():
                return True
            if alive_required:
                assert self.proc.poll() is None, (
                    f"process died:\n{self.log()[-4000:]}"
                )
            time.sleep(0.3)
        raise AssertionError(
            f"never saw {needle!r}:\n{self.log()[-4000:]}"
        )

    def wait_listening(self, marker, budget):
        deadline = time.monotonic() + budget
        while time.monotonic() < deadline:
            for line in self.log().splitlines():
                if marker in line:
                    port = line.rsplit(":", 1)[1].split()[0].strip("/")
                    self.base = f"http://127.0.0.1:{port}"
                    return self.base
            assert self.proc.poll() is None, (
                f"process died:\n{self.log()[-4000:]}"
            )
            time.sleep(0.3)
        raise AssertionError(f"never listened:\n{self.log()[-4000:]}")

    def terminate_and_wait(self, budget):
        import signal as signal_mod

        if self.proc.poll() is None:
            self.proc.send_signal(signal_mod.SIGTERM)
        try:
            rc = self.proc.wait(timeout=budget)
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
            self._log.close()
        return rc


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _post(url, payload, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _start_fleet(tmp_path, fleet_checkpoint, n=3, replica_extra=None,
                 router_extra=None):
    """3 advertise'd replicas + a router over one file KV, one shared
    telemetry dir; returns (replicas, router, telemetry_dir)."""
    kv = tmp_path / "fleetkv"
    tele = tmp_path / "telemetry"
    replicas = []
    for i in range(n):
        argv = [
            "--path", str(fleet_checkpoint),
            "--port", "0", "--serve-batch-size", "1",
            "--serve-buckets", "2", "--admission-capacity", "32",
            "--default-deadline-ms", "8000",
            "--drain-deadline", str(60 * _SCALE),
            "--advertise", "auto", "--fleet-kv", str(kv),
            "--replica-name", f"r{i}", "--replica-index", str(i),
            "--fleet-interval", "0.5",
            "--telemetry-dir", str(tele),
            "--jax-compilation-cache-dir", _JAX_CACHE,
        ] + list((replica_extra or {}).get(i, []))
        replicas.append(Proc(tmp_path, "serve", f"serve_r{i}", argv))
    router = Proc(tmp_path, "router", "router", [
        "--fleet-kv", str(kv), "--port", "0",
        "--fleet-interval", "0.5", "--fleet-timeout", "5",
        "--retry-budget", "2",
        "--default-deadline-ms", "8000",
        "--max-deadline-ms", "60000",
        "--telemetry-dir", str(tele),
    ] + list(router_extra or []))
    return replicas, router, tele


@pytest.mark.slow
def test_cli_fleet_replica_loss_sheds_and_traces(fleet_checkpoint,
                                                 tmp_path):
    """Acceptance e2e: 3 replicas + router; chaos kills replica 1 after
    its 3rd dispatched batch.  The router sheds around the death (zero
    failures after the in-flight window), names the replica-loss verdict
    within the lease timeout, and the merged trace tells the story."""
    replicas, router, tele = _start_fleet(
        tmp_path, fleet_checkpoint,
        replica_extra={1: ["--fault-inject", "replica-loss@3@1"]},
    )
    try:
        router.wait_listening("ROUTER listening", 60 * _SCALE)
        for r in replicas:
            r.wait_listening("SERVE listening", 120 * _SCALE)
        # the router becomes ready once the replicas' leases land
        deadline = time.monotonic() + 240 * _SCALE
        while time.monotonic() < deadline:
            code, body = _get(router.base + "/readyz")
            if code == 200 and body.get("routable", 0) == 3:
                break
            time.sleep(0.5)
        code, body = _get(router.base + "/readyz")
        assert code == 200 and body["routable"] == 3, (
            body, router.log()[-3000:]
        )

        # drive traffic from a small pool; replica 1 dies mid-run
        results = []  # (t, ok, code)
        stop = threading.Event()

        def drive():
            i = 0
            while not stop.is_set():
                t0 = time.monotonic()
                try:
                    code, _ = _post(
                        router.base + "/v1/infer",
                        {"tokens": [5, 6, 7], "deadline_ms": 8000,
                         "id": f"q{i}"},
                        timeout=30,
                    )
                except Exception:
                    code = -1
                results.append((t0, code == 200, code))
                i += 1
                time.sleep(0.05)

        threads = [threading.Thread(target=drive) for _ in range(4)]
        for t in threads:
            t.start()
        # wait for the kill (exit 74, no drain) then the named verdict
        deadline = time.monotonic() + 120 * _SCALE
        while time.monotonic() < deadline:
            if replicas[1].proc.poll() is not None:
                break
            time.sleep(0.3)
        assert replicas[1].proc.poll() == 74, replicas[1].log()[-2000:]
        killed_at = time.monotonic()
        router.wait_for("FLEET REPLICA-LOSS", 30 * _SCALE)
        assert "replica r1" in router.log()
        # let traffic run past the shed window, then stop
        time.sleep(8 * _SCALE)
        stop.set()
        for t in threads:
            t.join(timeout=30)

        post_window = killed_at + 4 * _SCALE
        failures = [r for r in results if not r[1]]
        late_failures = [r for r in failures if r[0] >= post_window]
        assert results, "no traffic was driven"
        # 100% minus in-flight: only requests in flight AT the kill may
        # fail (≤ pool size), and none after the shed window
        assert len(failures) <= 4, (len(failures), failures[:10])
        assert late_failures == [], late_failures
        code, stats = _get(router.base + "/stats")
        assert stats["ok"] >= len(results) - 4
        assert "r1" in stats["fleet"]["lost"]
    finally:
        router_rc = router.terminate_and_wait(60 * _SCALE)
        rcs = [r.terminate_and_wait(120 * _SCALE) for r in replicas]
    log = router.log()
    sys.stdout.write(log)  # CI smoke greps the router log via pytest -s
    assert router_rc == 0, log[-3000:]
    assert rcs[0] == 0 and rcs[2] == 0
    # the merged fleet timeline names the death for the post-mortem
    from unicore_tpu.telemetry import trace

    records = []
    for path in trace.find_journals(str(tele)):
        records.extend(trace.load_journal(path))
    summary = "\n".join(trace.summarize(trace.merge(records)))
    sys.stdout.write(summary + "\n")
    assert "replica r1 REPLICA-LOSS noticed by the router" in summary


@pytest.mark.slow
def test_cli_fleet_rolling_reload_halts_on_corrupt_candidate(
    fleet_checkpoint, tmp_path
):
    """Acceptance e2e: a corrupt published candidate HALTS the rolling
    reload after exactly one replica's RELOAD ROLLBACK — the other two
    replicas are never asked and the whole fleet keeps serving; a
    subsequent intact publish rolls all three."""
    ckpt_dir = tmp_path / "ckpt"
    ckpt_dir.mkdir()
    live = ckpt_dir / "checkpoint_last.pt"
    shutil.copy(fleet_checkpoint, live)
    pristine = tmp_path / "pristine.pt"
    shutil.copy(fleet_checkpoint, pristine)

    def publish(corrupt=False):
        staged = ckpt_dir / ".staged.tmp"
        shutil.copy(pristine, staged)
        if corrupt:
            size = os.path.getsize(staged)
            with open(staged, "r+b") as f:
                f.seek(int(size * 0.6))
                byte = f.read(1)
                f.seek(int(size * 0.6))
                f.write(bytes([byte[0] ^ 0xFF]))
        os.replace(staged, live)

    # every replica serves the live copy (POST /v1/reload always reloads
    # the replica's OWN --path) and the router watches the same file
    replicas, router, tele = _start_fleet(
        tmp_path, live, router_extra=[
            "--path", str(live), "--reload-interval", "0.5",
            "--reload-timeout", str(120 * _SCALE),
        ],
    )
    try:
        router.wait_listening("ROUTER listening", 60 * _SCALE)
        for r in replicas:
            r.wait_listening("SERVE listening", 120 * _SCALE)
        deadline = time.monotonic() + 240 * _SCALE
        while time.monotonic() < deadline:
            code, body = _get(router.base + "/readyz")
            if code == 200 and body.get("routable", 0) == 3:
                break
            time.sleep(0.5)
        code, _ = _post(router.base + "/v1/infer",
                        {"tokens": [5, 6, 7], "deadline_ms": 8000})
        assert code == 200

        # publish #1: corrupt — the roll must HALT after ONE rollback
        publish(corrupt=True)
        router.wait_for("ROLLING RELOAD HALT", 120 * _SCALE)
        rollback_logs = [
            i for i, r in enumerate(replicas)
            if "RELOAD ROLLBACK" in r.log()
        ]
        assert len(rollback_logs) == 1, (
            f"blast radius must be ONE replica, got {rollback_logs}"
        )
        assert "never asked" in router.log()
        # the fleet keeps serving the old snapshot
        code, _ = _post(router.base + "/v1/infer",
                        {"tokens": [8, 9], "deadline_ms": 8000})
        assert code == 200

        # publish #2: intact — the roll completes across all three
        publish(corrupt=False)
        router.wait_for("ROLLING RELOAD COMPLETE", 180 * _SCALE)
        assert all("RELOAD VERIFIED" in r.log() for r in replicas), (
            "every replica should verify+swap the intact candidate"
        )
        code, _ = _post(router.base + "/v1/infer",
                        {"tokens": [8, 9, 10], "deadline_ms": 8000})
        assert code == 200
    finally:
        router_rc = router.terminate_and_wait(60 * _SCALE)
        rcs = [r.terminate_and_wait(120 * _SCALE) for r in replicas]
    sys.stdout.write(router.log())  # CI smoke greps via pytest -s
    assert router_rc == 0
    assert all(rc == 0 for rc in rcs), rcs
