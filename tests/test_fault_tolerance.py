"""Deterministic chaos suite for the fault-tolerant distribution layer
(docs/FAULT_TOLERANCE.md): trainer liveness + barrier eviction on the
pserver, at-most-once RPC under injected wire faults (FaultyChannel),
crash-safe checkpoint/restore, master lease expiry, and real SIGKILL
process-death end-to-end.  Everything here is tier-1 (NOT `slow`): the
fault schedules are seeded/explicit, so each run exercises the identical
failure sequence."""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.distributed.faults import FaultSchedule, FaultyChannel
from paddle_tpu.distributed.master import MasterService
from paddle_tpu.distributed.ps_server import ParameterServer
from paddle_tpu.distributed.rpc import (
    PipelinedClient,
    RPCClient,
    VarServer,
    _backoff_wait,
)

_DIR = os.path.dirname(os.path.abspath(__file__))
_RUNNER = os.path.join(_DIR, "dist_mlp.py")


class _CountingService:
    """Parameter-state stand-in: every EXECUTION of `add` mutates state.
    Dedup holding means state == sum of logical calls, no matter how the
    wire mangled the frames."""

    def __init__(self):
        self.executions = 0
        self.state = 0.0
        self._lock = threading.Lock()

    def handle(self, verb, **kw):
        if verb == "add":
            with self._lock:
                self.executions += 1
                self.state += float(kw["value"])
                return {"ok": True, "state": self.state}
        if verb == "ping":
            return {"ok": True}
        return {"__error__": "unknown verb %s" % verb}


def _mk(service=None, **chan_kw):
    """VarServer + FaultyChannel in front of it."""
    svc = service if service is not None else _CountingService()
    srv = VarServer("127.0.0.1:0", svc).start()
    chan = FaultyChannel(srv.endpoint, **chan_kw).start()
    return svc, srv, chan


# ---------------------------------------------------------------------------
# wire-fault injection: at-most-once must hold under drop/dup/truncate
# ---------------------------------------------------------------------------

def test_fault_schedule_is_deterministic():
    a = FaultSchedule(seed=7, drop=0.3, dup=0.2)
    b = FaultSchedule(seed=7, drop=0.3, dup=0.2)
    seq_a = [a.next_action("c2s") for _ in range(50)]
    assert seq_a == [b.next_action("c2s") for _ in range(50)]
    # explicit pins override the random layer
    c = FaultSchedule({"c2s": {3: "truncate"}}, seed=7, drop=1.0)
    assert c.next_action("c2s")[1] == "drop"
    c.next_action("c2s"), c.next_action("c2s")
    assert c.next_action("c2s") == (3, "truncate")


def test_dup_request_executes_once_and_replies_stay_paired():
    """A duplicated request frame: the server's dedup executes ONCE, and
    the extra (req_id-tagged) reply must not shift later calls off by
    one."""
    svc, srv, chan = _mk(schedule={"c2s": {0: "dup"}})
    try:
        cli = RPCClient(chan.endpoint, timeout=5, retries=3, retry_wait=0.05)
        r1 = cli.call("add", value=10.0)
        assert r1["state"] == 10.0
        # the NEXT call must see its own reply, not the duplicate's
        r2 = cli.call("add", value=5.0)
        assert r2["state"] == 15.0
        assert svc.executions == 2 and svc.state == 15.0
        assert chan.stats["c2s"]["dup"] == 1
        cli.close()
    finally:
        chan.stop()
        srv.shutdown()


def test_dropped_request_is_retried_and_applied_once():
    svc, srv, chan = _mk(schedule={"c2s": {0: "drop"}})
    try:
        cli = RPCClient(chan.endpoint, timeout=0.5, retries=3,
                        retry_wait=0.05)
        assert cli.call("add", value=3.0)["state"] == 3.0
        assert svc.executions == 1 and svc.state == 3.0
        assert chan.stats["c2s"]["drop"] == 1
        cli.close()
    finally:
        chan.stop()
        srv.shutdown()


def test_dropped_reply_retry_hits_dedup_not_reexecution():
    """The at-most-once core: the server EXECUTED but its reply vanished;
    the client's replay must get the original result, not a double
    apply."""
    svc, srv, chan = _mk(schedule={"s2c": {0: "drop"}})
    try:
        cli = RPCClient(chan.endpoint, timeout=0.5, retries=3,
                        retry_wait=0.05)
        r = cli.call("add", value=7.0)
        assert r["state"] == 7.0
        assert svc.executions == 1, "retry re-executed a completed verb"
        assert svc.state == 7.0
        cli.close()
    finally:
        chan.stop()
        srv.shutdown()


def test_truncated_reply_mid_frame_retries_cleanly():
    """Peer dies mid-write: client sees a dead connection inside a frame,
    reconnects, replays — dedup keeps it at-most-once."""
    svc, srv, chan = _mk(schedule={"s2c": {0: "truncate"}})
    try:
        cli = RPCClient(chan.endpoint, timeout=2, retries=3, retry_wait=0.05)
        assert cli.call("add", value=2.0)["state"] == 2.0
        assert svc.executions == 1 and svc.state == 2.0
        assert chan.stats["s2c"]["truncate"] == 1
        cli.close()
    finally:
        chan.stop()
        srv.shutdown()


def test_corrupt_request_frame_rejected_and_retried_exactly_once():
    """Bit-rot on the wire (one payload byte flipped): the server's
    closed-type decode rejects the frame as a protocol violation and
    drops the connection; the client's reconnect + replay applies the
    verb exactly once — the transport sibling of the journal's
    crc-framed tail-skip discipline."""
    svc, srv, chan = _mk(schedule={"c2s": {0: "corrupt"}})
    try:
        cli = RPCClient(chan.endpoint, timeout=2, retries=5,
                        retry_wait=0.05)
        assert cli.call("add", value=4.0)["state"] == 4.0
        assert svc.executions == 1 and svc.state == 4.0
        assert chan.stats["c2s"]["corrupt"] == 1
        cli.close()
    finally:
        chan.stop()
        srv.shutdown()


def test_param_state_survives_seeded_fault_soup():
    """20 logical sends through a channel randomly dropping/duplicating/
    delaying/truncating frames (seeded): the accumulated 'parameter'
    must equal the exact sum — no lost and no double-applied update."""
    # seed 5 verified deterministic: 5 drops + 6 dups + 9 delays injected,
    # identical stats run over run (the schedule is consumed in the
    # client's serial request/reply order)
    svc, srv, chan = _mk(seed=5, drop=0.12, dup=0.15, truncate=0.05,
                         delay=0.1, delay_s=0.02)
    try:
        cli = RPCClient(chan.endpoint, timeout=0.4, retries=6,
                        retry_wait=0.05)
        total = 0.0
        for i in range(20):
            v = float(i + 1)
            total += v
            cli.call("add", value=v)
        assert svc.state == total, (svc.state, total, chan.stats)
        assert svc.executions == 20, (svc.executions, chan.stats)
        # the schedule really fired: at least one injected fault
        injected = sum(
            chan.stats[d][a]
            for d in ("c2s", "s2c") for a in ("drop", "dup", "truncate"))
        assert injected > 0, chan.stats
        cli.close()
    finally:
        chan.stop()
        srv.shutdown()


def test_pserver_async_grads_exact_under_wire_faults():
    """The real ParameterServer verb path (async sends) behind a faulty
    wire: every grad applies exactly once, in order."""
    ps = ParameterServer([None], {"g": 0}, num_trainers=1, sync_mode=False)
    applied = []
    ps._apply_shard = lambda idx, feed: applied.append(
        float(np.asarray(feed["g"]).reshape(-1)[0]))
    srv = VarServer("127.0.0.1:0", ps).start()
    chan = FaultyChannel(srv.endpoint,
                         schedule={"c2s": {1: "dup"}, "s2c": {3: "drop"}},
                         ).start()
    try:
        cli = RPCClient(chan.endpoint, timeout=0.75, retries=5,
                        retry_wait=0.05)
        for i in range(6):
            cli.send_var("g", np.full((1,), float(i)), trainer_id=0)
        assert applied == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0], (
            applied, chan.stats)
        cli.close()
    finally:
        chan.stop()
        srv.shutdown()


def test_pipelined_window_at_most_once_under_fault_soup():
    """comm_inflight > 1: four calls in flight at once through a wire
    duplicating and delaying frames (the faults that stress DEDUP and
    REORDERING under overlap — a dup'd request must apply once even
    while three other calls race it; delays shuffle completion order) —
    every logical add still applies exactly once.  Destructive faults
    (drop/truncate) are call-fatal only after the replay budget and the
    schedule's frame->call mapping races across workers, so they are
    exercised through the window serially below, where the schedule is
    deterministic."""
    svc, srv, chan = _mk(seed=11, dup=0.2, delay=0.15, delay_s=0.02)
    pipe = PipelinedClient(chan.endpoint, window=4, timeout=2, retries=6,
                           retry_wait=0.05)
    try:
        total = 0.0
        for i in range(24):
            v = float(i + 1)
            total += v
            pipe.submit("add", value=v)
        results = pipe.drain()
        assert len(results) == 24
        assert svc.state == total, (svc.state, total, chan.stats)
        assert svc.executions == 24, (svc.executions, chan.stats)
        injected = chan.stats["c2s"]["dup"] + chan.stats["s2c"]["dup"]
        assert injected > 0, chan.stats
    finally:
        pipe.close()
        chan.stop()
        srv.shutdown()


def test_pipelined_interface_survives_destructive_faults_serially():
    """Same submit/drain machinery, window=1 (one worker consumes the
    schedule serially, so the pinned drop/truncate land deterministically):
    a dropped request, a dropped reply, and a truncated frame each retry
    through the window client and apply exactly once."""
    svc, srv, chan = _mk(schedule={"c2s": {1: "truncate"},
                                   "s2c": {5: "drop"}})
    pipe = PipelinedClient(chan.endpoint, window=1, timeout=0.5, retries=6,
                           retry_wait=0.05)
    try:
        total = 0.0
        for i in range(8):
            v = float(i + 1)
            total += v
            pipe.submit("add", value=v)
        results = pipe.drain()
        assert len(results) == 8
        assert svc.state == total, (svc.state, total, chan.stats)
        assert svc.executions == 8, (svc.executions, chan.stats)
        assert chan.stats["c2s"]["truncate"] == 1
        assert chan.stats["s2c"]["drop"] == 1
    finally:
        pipe.close()
        chan.stop()
        srv.shutdown()


def test_pipelined_drain_surfaces_failure_after_letting_rest_finish():
    """One call in the window dies (unknown verb -> remote error): drain
    must raise it, and the other in-flight calls still complete."""
    svc, srv, chan = _mk()
    pipe = PipelinedClient(chan.endpoint, window=3, timeout=2, retries=3)
    try:
        pipe.submit("add", value=1.0)
        pipe.submit("no_such_verb")
        pipe.submit("add", value=2.0)
        with pytest.raises(RuntimeError):
            pipe.drain()
        assert svc.state == 3.0 and svc.executions == 2
        assert pipe.drain() == []  # window is clean afterwards
    finally:
        pipe.close()
        chan.stop()
        srv.shutdown()


def test_bucketed_sync_round_with_folded_barrier_and_eviction():
    """The bucketed wire path under the liveness layer: trainer 1 ships
    one of its two declared buckets then dies; the reaper evicts it, the
    survivor's folded barrier (last-bucket arrival) completes the round
    with ONLY the survivor's grads, and the ghost's partial bucket is
    dropped."""
    ps = ParameterServer([None, None], {"g0": 0, "g1": 1}, num_trainers=2,
                         sync_mode=True, eviction_deadline=0.6)
    applied = []
    ps._apply_shard = lambda idx, feed: applied.append(
        {k: np.asarray(v).copy() for k, v in feed.items()})
    srv = VarServer("127.0.0.1:0", ps).start()
    try:
        cli = RPCClient(srv.endpoint, timeout=30, retries=3)
        # trainer 1 heartbeats (tracked), ships bucket 1 of 2... and dies
        cli.call("heartbeat", trainer_id=1)
        cli.call("send_bucket", blocks={"g0": np.full((2,), 100.0)},
                 trainer_id=1, seq_total=2)
        # trainer 0 ships both buckets; the second is its send barrier
        cli.call("send_bucket", blocks={"g0": np.full((2,), 3.0)},
                 trainer_id=0, seq_total=2)
        t0 = time.monotonic()
        r = cli.call("send_bucket", blocks={"g1": np.full((2,), 5.0)},
                     trainer_id=0, seq_total=2)
        # the eviction minted a plan epoch at the boundary, and the
        # post-round reply carries it (elastic autoscaling)
        assert r == {"ok": True, "pepoch": 1}
        assert time.monotonic() - t0 < 5.0, "folded barrier hung"
        assert ps._round == 1 and ps._live == {0} and 1 in ps._evicted
        merged = {}
        for d in applied:
            merged.update(d)
        np.testing.assert_array_equal(merged["g0"], np.full((2,), 3.0))
        np.testing.assert_array_equal(merged["g1"], np.full((2,), 5.0))
        # the ghost's next bucket is told it is dead
        assert cli.call("send_bucket", blocks={"g0": np.zeros(2)},
                        trainer_id=1, seq_total=2)["evicted"]
        # bucketed fetch with folded fetch barrier resets the round
        out = cli.call("get_bucket", names=[], trainer_id=0, fetch_total=1)
        assert out == {}
        assert ps._params_ready is False
        cli.close()
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# client hardening: backoff + per-call deadline
# ---------------------------------------------------------------------------

def test_backoff_grows_exponentially_with_jitter():
    lows = [_backoff_wait(a, 0.1) for a in range(4)]
    for a, w in enumerate(lows):
        span = min(5.0, 0.1 * 2 ** a)
        assert span / 2 <= w <= span, (a, w)
    # cap: huge attempts stay bounded
    assert _backoff_wait(30, 0.1) <= 5.0


def test_call_deadline_bounds_connect_retries():
    """deadline_s bounds the WHOLE call: a dead endpoint with a huge
    retry budget must fail within the deadline, not retries x timeout."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    ep = "127.0.0.1:%d" % s.getsockname()[1]
    s.close()  # nothing listens here now
    cli = RPCClient(ep, timeout=5, retries=1000, retry_wait=0.05)
    t0 = time.monotonic()
    with pytest.raises((ConnectionError, OSError)):
        cli.call("ping", deadline_s=1.0)
    assert time.monotonic() - t0 < 5.0
    cli.close()


def test_client_survives_server_restart_on_same_port():
    """Kill-and-restart window: the cached connection dies, the client
    reconnects against the RESTARTED server and the verb resolves against
    its (restored) state."""
    svc1 = _CountingService()
    srv1 = VarServer("127.0.0.1:0", svc1).start()
    ep = srv1.endpoint
    cli = RPCClient(ep, timeout=2, retries=20, retry_wait=0.05)
    try:
        assert cli.call("add", value=1.0)["ok"]
        srv1.shutdown()
        # restart on the SAME endpoint with restored state
        svc2 = _CountingService()
        svc2.state = svc1.state  # the "checkpoint restore"
        srv2 = VarServer(ep, svc2).start()
        try:
            r = cli.call("add", value=2.0)
            assert r["state"] == 3.0  # resumed from restored state
        finally:
            srv2.shutdown()
    finally:
        cli.close()


# ---------------------------------------------------------------------------
# liveness + eviction (in-process)
# ---------------------------------------------------------------------------

def test_dead_trainer_evicted_and_sync_round_completes():
    """THE deadlock the liveness layer exists to break: trainer 1 is
    heartbeat-tracked, then goes silent mid-round; trainer 0's send
    barrier must complete within the eviction deadline instead of
    hanging forever."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=2, sync_mode=True,
                         eviction_deadline=0.6)
    applied = []
    ps._apply_shard = lambda idx, feed: applied.append(
        np.asarray(feed["g0"]).copy())
    srv = VarServer("127.0.0.1:0", ps).start()
    try:
        cli = RPCClient(srv.endpoint, timeout=30, retries=3)
        # trainer 1: alive long enough to be tracked and contribute a
        # grad... then dies (no more heartbeats, no barrier)
        cli.call("heartbeat", trainer_id=1)
        cli.send_var("g0", np.full((2,), 100.0), trainer_id=1)
        # trainer 0: sends its grad and enters the barrier
        cli.send_var("g0", np.full((2,), 3.0), trainer_id=0)
        t0 = time.monotonic()
        r = cli.barrier("send", trainer_id=0)
        elapsed = time.monotonic() - t0
        assert r["ok"] is True
        assert elapsed < 5.0, "barrier hung %.1fs — eviction failed" % elapsed
        # round ran with ONLY the survivor's grad: the ghost's unsummed
        # contribution was dropped, not averaged in
        assert len(applied) == 1
        np.testing.assert_array_equal(applied[0], np.full((2,), 3.0))
        assert ps._round == 1
        assert ps._live == {0} and 1 in ps._evicted
        # fetch barrier now needs only the survivor
        assert cli.barrier("fetch", trainer_id=0)["ok"] is True
        # the ghost coming back learns it is dead (and is NOT re-admitted)
        hb = cli.call("heartbeat", trainer_id=1)
        assert hb["live"] is False
        assert cli.call("barrier", kind="send", trainer_id=1)["evicted"]
        # the ghost's exit-path complete() is already accounted for by
        # the eviction: it must NOT pop the survivor from the live set
        cli.call("complete", trainer_id=1)
        assert ps._live == {0} and not ps._done.is_set()
        cli.close()
    finally:
        srv.shutdown()


def test_trainer_evicted_while_blocked_in_barrier_learns_immediately():
    """A tracked trainer that goes silent WHILE parked inside the send
    barrier must be woken by its own eviction with evicted=True — not
    handed {ok: True} for a round it was removed from, and not left
    blocked until some other trainer completes a round."""
    ps = ParameterServer({}, {}, num_trainers=2, sync_mode=True,
                         eviction_deadline=0.5)
    srv = VarServer("127.0.0.1:0", ps).start()
    try:
        cli = RPCClient(srv.endpoint, timeout=30, retries=3)
        cli.call("heartbeat", trainer_id=1)  # tracked...
        out = []

        def ghost_barrier():
            # ...then its heartbeat thread dies while it waits here
            out.append(cli.call("barrier", kind="send", trainer_id=1))

        th = threading.Thread(target=ghost_barrier, daemon=True)
        th.start()
        th.join(timeout=10)
        assert not th.is_alive(), "evicted trainer still parked in barrier"
        assert out and out[0] == {"ok": False, "evicted": True}, out
        assert ps._live == {0}
        cli.close()
    finally:
        srv.shutdown()


def test_eviction_with_stale_fetch_barrier_does_not_hang_survivor():
    """Re-evaluation ORDER bug: the survivor fetched round R (its fetch
    barrier pends on the ghost) and is parked in its round-R+1 send
    barrier when the ghost is evicted.  Re-evaluating the stale fetch
    barrier AFTER _run_round would flip the fresh round's params_ready
    back off and hang the survivor's next get forever — fetch must
    re-evaluate first."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=2, sync_mode=True,
                         eviction_deadline=0.5)
    ps._apply_shard = lambda idx, feed: None
    ps.scope.set("p.block0", np.zeros(2, np.float32))
    srv = VarServer("127.0.0.1:0", ps).start()
    try:
        cli = RPCClient(srv.endpoint, timeout=30, retries=3)
        # round 1: both trainers send + barrier, then trainer 0 fetches
        cli.call("heartbeat", trainer_id=1)
        for tid in (0, 1):
            cli.send_var("g0", np.ones(2), trainer_id=tid)
        done = []
        t = threading.Thread(target=lambda: done.append(
            cli.call("barrier", kind="send", trainer_id=0)), daemon=True)
        t.start()
        cli2 = RPCClient(srv.endpoint, timeout=30, retries=3)
        cli2.call("barrier", kind="send", trainer_id=1)
        t.join(10)
        assert done and ps._round == 1
        cli.get_var("p.block0", trainer_id=0)
        cli.call("barrier", kind="fetch", trainer_id=0)  # pends on ghost
        # round 2: trainer 0 sends and parks in its send barrier; the
        # ghost (trainer 1) has gone silent and gets evicted meanwhile
        cli.send_var("g0", np.ones(2), trainer_id=0)
        t0 = time.monotonic()
        r = cli.barrier("send", trainer_id=0)
        assert r["ok"] is True and time.monotonic() - t0 < 10
        assert ps._round == 2 and ps._live == {0}
        # THE regression: round 2's params must be fetchable — before the
        # ordering fix the stale round-1 fetch barrier reset params_ready
        # after round 2 ran, and this get blocked forever (threaded with
        # a bounded join so a regression fails fast instead of hanging)
        got = []
        g = threading.Thread(target=lambda: got.append(
            cli.get_var("p.block0", trainer_id=0)), daemon=True)
        g.start()
        g.join(10)
        assert got, "round-2 get hung: stale fetch barrier reset " \
            "params_ready after the eviction round ran"
        assert np.asarray(got[0]).shape == (2,)
        assert ps._params_ready is True
        cli.close()
        cli2.close()
    finally:
        srv.shutdown()


def test_untracked_trainers_are_never_evicted():
    """No heartbeats => the exact pre-liveness contract: nothing times
    out, the barrier waits for everyone."""
    ps = ParameterServer({}, {}, num_trainers=2, sync_mode=True,
                         eviction_deadline=0.2)
    srv = VarServer("127.0.0.1:0", ps).start()
    try:
        cli0 = RPCClient(srv.endpoint, timeout=10, retries=3)
        done = []

        def t0_barrier():
            done.append(cli0.call("barrier", kind="send", trainer_id=0))

        th = threading.Thread(target=t0_barrier, daemon=True)
        th.start()
        time.sleep(0.6)  # 3x the deadline: nobody tracked, nobody evicted
        assert not done and ps._live == {0, 1} and not ps._evicted
        # trainer 1 arrives late and the round completes normally
        cli1 = RPCClient(srv.endpoint, timeout=10, retries=3)
        cli1.call("barrier", kind="send", trainer_id=1)
        th.join(timeout=10)
        assert done and done[0]["ok"] is True and ps._round == 1
        cli0.close()
        cli1.close()
    finally:
        srv.shutdown()


def test_eviction_drops_queued_sparse_rows():
    ps = ParameterServer(
        {}, {}, num_trainers=2, sync_mode=True, eviction_deadline=0.5,
        sparse_tables={"t0": {"tbl": np.zeros((4, 2), np.float32),
                              "lr": 0.1,
                              "opt": {"type": "sgd", "attrs": {}}}})
    ps._h_heartbeat(trainer_id=1)
    ps._h_send_sparse("t0", np.array([1]),
                      np.full((1, 2), 100.0, np.float32), trainer_id=1)
    ps._h_send_sparse("t0", np.array([2]),
                      np.ones((1, 2), np.float32), trainer_id=0)
    with ps._cv:
        ps._evict_locked(1, "test")
    assert [tid for tid, _tbl in ps._pending_sparse] == [0]
    with ps._cv:
        ps._run_round()
    tbl = ps.sparse_tables["t0"]["tbl"]
    np.testing.assert_allclose(tbl[2], -0.1 * np.ones(2), rtol=1e-6)
    np.testing.assert_allclose(tbl[1], np.zeros(2))  # ghost's row dropped


def test_all_trainers_dead_sets_done():
    ps = ParameterServer({}, {}, num_trainers=1, sync_mode=True,
                         eviction_deadline=0.3)
    ps._h_heartbeat(trainer_id=0)
    t0 = time.monotonic()
    assert ps.wait_done(timeout=5), "done never set after last eviction"
    assert time.monotonic() - t0 < 5.0


# ---------------------------------------------------------------------------
# crash-safe checkpoints
# ---------------------------------------------------------------------------

def test_checkpoint_writes_manifest_and_restores(tmp_path):
    ps = ParameterServer({}, {}, num_trainers=1, sync_mode=False,
                         checkpoint_dir=str(tmp_path), server_idx=0)
    ps.scope.set("w.block0", np.arange(4, dtype=np.float32))
    ps._round = 7
    assert ps.save_checkpoint()
    mpath = tmp_path / "pserver_0.manifest.json"
    assert mpath.exists()
    manifest = json.loads(mpath.read_text())
    assert manifest["round"] == 7
    assert manifest["file"] == "pserver_0.ckpt"
    # a fresh server restores round + vars
    ps2 = ParameterServer({}, {}, num_trainers=1, sync_mode=False,
                          checkpoint_dir=str(tmp_path), server_idx=0)
    assert ps2.load_checkpoint() == 7
    np.testing.assert_array_equal(
        np.asarray(ps2.scope.find_var("w.block0")),
        np.arange(4, dtype=np.float32))


def test_stale_manifest_over_complete_snapshot_recovers(tmp_path):
    """The routine SIGKILL window: the kill lands between the snapshot
    rename and the manifest rename, leaving the PREVIOUS round's manifest
    next to a complete new snapshot.  Restore must recognize this (the
    snapshot parses cleanly), restore from it, and repair the manifest —
    not throw away good state."""
    ps = ParameterServer({}, {}, num_trainers=1, sync_mode=False,
                         checkpoint_dir=str(tmp_path), server_idx=0)
    ps.scope.set("v", np.ones(2, np.float32))
    ps._round = 3
    assert ps.save_checkpoint()
    stale_manifest = (tmp_path / "pserver_0.manifest.json").read_bytes()
    ps.scope.set("v", np.full(2, 9.0, np.float32))
    ps._round = 5
    assert ps.save_checkpoint()
    # simulate the crash: new snapshot on disk, OLD manifest beside it
    (tmp_path / "pserver_0.manifest.json").write_bytes(stale_manifest)
    ps2 = ParameterServer({}, {}, num_trainers=1, sync_mode=False,
                          checkpoint_dir=str(tmp_path), server_idx=0)
    assert ps2.load_checkpoint() == 5
    np.testing.assert_array_equal(np.asarray(ps2.scope.find_var("v")),
                                  np.full(2, 9.0, np.float32))
    # the manifest was repaired to match the snapshot it sits beside
    fixed = json.loads((tmp_path / "pserver_0.manifest.json").read_text())
    assert fixed["round"] == 5


@pytest.mark.parametrize("corruption", ["truncate", "garbage", "empty"])
def test_corrupt_checkpoint_is_skipped_not_fatal(tmp_path, corruption):
    """A torn/corrupt snapshot must produce a COLD start (None), never a
    crash-looping pserver."""
    ps = ParameterServer({}, {}, num_trainers=1, sync_mode=False,
                         checkpoint_dir=str(tmp_path), server_idx=0)
    ps.scope.set("v", np.ones(3, np.float32))
    ps._round = 3
    assert ps.save_checkpoint()
    path = tmp_path / "pserver_0.ckpt"
    raw = path.read_bytes()
    if corruption == "truncate":
        path.write_bytes(raw[: len(raw) // 2])
    elif corruption == "garbage":
        path.write_bytes(b"\x00" * len(raw))
    else:
        path.write_bytes(b"")
    ps2 = ParameterServer({}, {}, num_trainers=1, sync_mode=False,
                          checkpoint_dir=str(tmp_path), server_idx=0)
    assert ps2.load_checkpoint() is None


# ---------------------------------------------------------------------------
# master: lease expiry + dedup under injected faults
# ---------------------------------------------------------------------------

def test_master_lease_expiry_under_injected_faults():
    """A trainer leases a task and dies; the lease times out and the task
    goes back to the queue for the survivor — all through a wire that
    duplicates and drops frames (retries + the master's own idempotency
    must absorb them)."""
    svc = MasterService(timeout_s=0.5, failure_max=3, chunks_per_task=1)
    srv = VarServer("127.0.0.1:0", svc).start()
    chan = FaultyChannel(srv.endpoint,
                         schedule={"c2s": {1: "dup"},
                                   "s2c": {2: "drop"}}).start()
    try:
        cli = RPCClient(chan.endpoint, timeout=0.75, retries=6,
                        retry_wait=0.05)
        r = cli.call("set_dataset", chunks=["c0", "c1"], trainer_id=0)
        assert r["ok"]
        # trainer 0 leases a task... and dies without finishing it
        lease = cli.call("get_task", trainer_id=0)
        assert lease["task"] is not None
        dead_tid = lease["task"]["id"]
        # survivor drains the queue; the expired lease must come back
        got, deadline = [], time.monotonic() + 10
        while len(got) < 2 and time.monotonic() < deadline:
            r = cli.call("get_task", trainer_id=1)
            if r.get("task") is None:
                time.sleep(0.1)
                continue
            got.append(r["task"]["id"])
            cli.call("task_finished", task_id=r["task"]["id"], trainer_id=1)
        assert sorted(got).count(dead_tid) == 1, got
        assert len(got) == 2, "lease never expired back to the queue"
        stats = cli.call("num_done", trainer_id=1)
        assert stats == {"done": 2, "todo": 0, "pending": 0}
        # lease-expiry bumped the failure count exactly once
        assert svc._done[-1].failures + svc._done[-2].failures == 1
        cli.close()
    finally:
        chan.stop()
        srv.shutdown()


def test_master_restart_requeues_leases_and_survives_corrupt_snapshot(
        tmp_path):
    snap = str(tmp_path / "master.json")
    svc = MasterService(timeout_s=60, snapshot_path=snap)
    svc._h_set_dataset(chunks=["a", "b"])
    lease = svc._h_get_task(trainer_id=0)
    assert lease["task"] is not None
    # master "dies"; the restart folds the leased task back into todo
    svc2 = MasterService(timeout_s=60, snapshot_path=snap)
    assert len(svc2._todo) == 2 and not svc2._pending
    # a torn snapshot file must mean a cold start, not a crash loop
    with open(snap, "w") as f:
        f.write('{"todo": [tor')
    svc3 = MasterService(timeout_s=60, snapshot_path=snap)
    assert svc3._todo == [] and svc3._done == [] and not svc3._dataset_set


# ---------------------------------------------------------------------------
# launch.py chaos helpers
# ---------------------------------------------------------------------------

def test_cluster_kill_one_is_expected_failure():
    from paddle_tpu.distributed.launch import _Cluster

    cluster = _Cluster()
    env = dict(os.environ)
    cluster.spawn("victim", [sys.executable, "-c",
                             "import time; time.sleep(60)"], env)
    cluster.spawn("survivor", [sys.executable, "-c",
                               "print('fine')"], env)
    cluster.schedule_kill("victim", 0.2)
    rc = cluster.wait()
    assert rc == 0, "deliberate SIGKILL leaked into the cluster exit code"
    assert cluster.proc("victim").returncode != 0


def test_control_call_passes_endpoint_kwarg_through():
    """Regression: _control_call's own first parameter was named
    `endpoint`, shadowing the attach_worker/report_pool_death verbs'
    `endpoint` kwarg (TypeError: multiple values for argument) — the
    launcher could never attach a process-mode pool worker."""
    from paddle_tpu.distributed.launch import _control_call

    class _Ctl:
        def handle(self, verb, **kw):
            return {"verb": verb, "echo": kw.get("endpoint")}

    srv = VarServer("127.0.0.1:0", _Ctl()).start()
    try:
        r = _control_call(srv.endpoint, "attach_worker",
                          endpoint="10.0.0.1:99")
        assert r == {"verb": "attach_worker", "echo": "10.0.0.1:99"}
    finally:
        srv.shutdown()
        with RPCClient._lock:
            RPCClient._instances.pop(srv.endpoint, None)


def test_cluster_aux_children_do_not_hold_job_open():
    """Regression: process-mode pool workers serve RPC until told to
    stop, so cluster.wait() used to hang forever once the training job
    completed.  Aux children are excluded from the conclusion scan and
    retired when the job concludes."""
    from paddle_tpu.distributed.launch import _Cluster

    cluster = _Cluster()
    env = dict(os.environ)
    cluster.spawn("pool_worker.0", [sys.executable, "-c",
                  "import time; time.sleep(120)"], env, aux=True)
    cluster.spawn("trainer.0", [sys.executable, "-c",
                  "print('done')"], env)
    t0 = time.monotonic()
    assert cluster.wait() == 0
    assert time.monotonic() - t0 < 60, "wait() hung on the aux child"
    p = cluster.proc("pool_worker.0")
    assert p.poll() is not None, "aux child not retired at conclusion"


def test_cluster_aux_death_never_fails_the_job():
    """A service child dying (pool_proc_kill chaos, OOM) degrades
    serving; it must not take the training job down with it."""
    from paddle_tpu.distributed.launch import _Cluster

    cluster = _Cluster()
    env = dict(os.environ)
    cluster.spawn("pool_worker.0", [sys.executable, "-c",
                  "import sys; sys.exit(3)"], env, aux=True)
    cluster.spawn("trainer.0", [sys.executable, "-c",
                  "import time; time.sleep(1.0); print('done')"], env)
    assert cluster.wait() == 0


def test_launcher_reports_trainer_death_to_pserver():
    """The pre-heartbeat kill window: a trainer that dies BEFORE its
    first pserver contact was never tracked, so liveness eviction can't
    see it — the LAUNCHER's death report (the `evict` verb) must shrink
    the live set AND drop the ghost's partial round contribution so the
    sync round completes cleanly."""
    from paddle_tpu.distributed.launch import _Cluster

    ps = ParameterServer([None], {"g0": 0}, num_trainers=2, sync_mode=True)
    applied = []
    ps._apply_shard = lambda idx, feed: applied.append(
        np.asarray(feed["g0"]).copy())
    srv = VarServer("127.0.0.1:0", ps).start()
    try:
        cli = RPCClient(srv.endpoint, timeout=10, retries=3)
        # the doomed trainer got HALF its state out before dying: one
        # grad and its barrier, which must NOT count toward the round
        cli.send_var("g0", np.full((2,), 100.0), trainer_id=1)
        cli.call("barrier", kind="fetch", trainer_id=1)  # stale entry
        cluster = _Cluster()

        # the launch_pserver wiring, minus the jax-importing children
        def notify(tag, rc):
            if tag.startswith("trainer."):
                RPCClient(srv.endpoint, timeout=2, retries=2).call(
                    "evict", trainer_id=int(tag.split(".", 1)[1]),
                    deadline_s=5.0)

        cluster.on_child_death = notify
        cluster.spawn("trainer.1", [sys.executable, "-c",
                                    "import sys; sys.exit(3)"],
                      dict(os.environ))
        cluster.expect_failure("trainer.1")
        assert cluster.wait() == 0
        assert ps._live == {0}, "death report never reached pserver"
        # the survivor's round uses ONLY its own grads
        cli.send_var("g0", np.full((2,), 3.0), trainer_id=0)
        assert cli.call("barrier", kind="send", trainer_id=0)["ok"]
        assert ps._round == 1
        assert len(applied) == 1
        np.testing.assert_array_equal(applied[0], np.full((2,), 3.0))
        cli.close()
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# end-to-end process death (real SIGKILL, real cluster)
# ---------------------------------------------------------------------------

def _spawn(env):
    full = dict(os.environ)
    full.update(env)
    full["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, _RUNNER], env=full,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _losses(proc, timeout=240):
    out, err = proc.communicate(timeout=timeout)
    assert proc.returncode == 0, "runner failed:\n%s\n%s" % (out, err)
    for line in out.splitlines():
        if line.startswith("LOSSES "):
            return json.loads(line[len("LOSSES "):]), out
    raise AssertionError("no LOSSES line in output:\n%s\n%s" % (out, err))


def _wait_port(port, timeout=60):
    t0 = time.time()
    while time.time() - t0 < timeout:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=1).close()
            return
        except OSError:
            time.sleep(0.2)
    raise TimeoutError("pserver port %d never opened" % port)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _trainer_losses(out, tag):
    """Parse one trainer's LOSSES line out of [tag]-prefixed cluster
    output."""
    for ln in out.splitlines():
        if ln.startswith("[%s] LOSSES " % tag):
            return json.loads(ln[len("[%s] LOSSES " % tag):])
    raise AssertionError("no LOSSES line for %s in:\n%s" % (tag, out))


def test_supervised_pserver_sigkill_restores_and_job_completes(
        tmp_path, capfd):
    """ACCEPTANCE (tentpole): a SIGKILL'd pserver under supervision
    restarts from its manifest checkpoint, mints a new incarnation, the
    trainer fences the restart (replaying the in-flight round), and the
    sync dist MLP job runs to completion with finite loss.  The kill
    trigger is a FENCE — the first checkpointed round's manifest exists
    — not a timer."""
    from paddle_tpu.distributed.launch import _Cluster, _RestartPolicy

    port = _free_port()
    eps = "127.0.0.1:%d" % port
    ckpt = str(tmp_path / "ckpt")
    steps = 8
    full = dict(os.environ)
    full.update({
        "PADDLE_PSERVER_EPS": eps,
        "PADDLE_TRAINERS": "1",
        "DIST_SYNC_MODE": "1",
        "DIST_STEPS": str(steps),
        "DIST_STEP_SLEEP": "0.2",
        "PADDLE_PSERVER_CKPT_DIR": ckpt,
        "PADDLE_PSERVER_CKPT_EVERY": "1",
        "FLAGS_max_retry": "120",
        "JAX_PLATFORMS": "cpu",
    })
    cmd = [sys.executable, "-u", _RUNNER]
    ps_env = dict(full, PADDLE_TRAINING_ROLE="PSERVER",
                  PADDLE_CURRENT_ENDPOINT=eps)
    cluster = _Cluster()
    cluster.supervise("pserver.0", cmd, ps_env,
                      _RestartPolicy(max_restarts=3, backoff_s=0.2))
    cluster.spawn("pserver.0", cmd, ps_env)
    try:
        _wait_port(port)
        cluster.spawn("trainer.0", cmd,
                      dict(full, PADDLE_TRAINING_ROLE="TRAINER",
                           PADDLE_TRAINER_ID="0"))
        # FENCE: round >= 1 has been checkpointed (manifest landed) —
        # any kill from here on must be recoverable
        manifest = os.path.join(ckpt, "pserver_0.manifest.json")
        t0 = time.time()
        while time.time() - t0 < 120 and not os.path.exists(manifest):
            time.sleep(0.05)
        assert os.path.exists(manifest), "no checkpoint before the kill"
        cluster.proc("pserver.0").kill()  # real mid-job SIGKILL
        rc = cluster.wait()
    finally:
        cluster.kill()
    out = capfd.readouterr().out
    assert rc == 0, out
    assert cluster.restarts.get("pserver.0", 0) >= 1, \
        "supervisor never restarted the killed pserver"
    assert "PSERVER RESTORED" in out, out
    losses = _trainer_losses(out, "trainer.0")
    assert len(losses) == steps
    assert np.isfinite(losses).all(), losses
    # recovery observability: the trainer witnessed the restart
    for ln in out.splitlines():
        if ln.startswith("[trainer.0] COUNTERS "):
            c = json.loads(ln[len("[trainer.0] COUNTERS "):])
            assert c["pserver_restarts_seen"] >= 1, c
            break
    else:
        raise AssertionError("no COUNTERS line:\n%s" % out)


def test_supervised_trainer_relaunch_rejoins_at_round_boundary(
        tmp_path, capfd):
    """ACCEPTANCE (tentpole): a killed trainer under supervision
    relaunches, the launcher evicts the ghost THEN pre-registers the id
    (so the job survives the boot window), the pserver readmits it at a
    round boundary, and BOTH trainers finish with finite losses.  The
    crash trigger is a fence (self-SIGKILL after step 1, once — marker
    file), not a timer."""
    from paddle_tpu.distributed.launch import launch_pserver

    marker = str(tmp_path / "crash_once")
    env = dict(os.environ)
    steps = 6
    env.update({
        "DIST_STEPS": str(steps),
        "DIST_STEP_SLEEP": "0.25",
        "DIST_CRASH_RANK": "1",
        "DIST_CRASH_AFTER_STEP": "1",
        "DIST_CRASH_ONCE": marker,
        "FLAGS_max_retry": "120",
        "JAX_PLATFORMS": "cpu",
    })
    rc = launch_pserver([_RUNNER], nproc=2, n_pservers=1, base_env=env,
                        sync=True, supervise=True, restart_backoff=0.2)
    out = capfd.readouterr().out
    assert rc == 0, out
    assert os.path.exists(marker), "the chaos crash never fired"
    assert "PSERVER EVICT trainer=1" in out, out
    assert "PSERVER READMIT trainer=1" in out, out
    l0 = _trainer_losses(out, "trainer.0")
    l1 = _trainer_losses(out, "trainer.1")
    assert len(l0) == steps and np.isfinite(l0).all(), l0
    assert len(l1) == steps and np.isfinite(l1).all(), l1
    # the pserver's final stats agree: one eviction, one readmission
    for ln in out.splitlines():
        if ln.startswith("[pserver.0] PSERVER-STATS "):
            s = json.loads(ln[len("[pserver.0] PSERVER-STATS "):])
            assert s["evictions"] == 1 and s["readmissions"] == 1, s
            break
    else:
        raise AssertionError("no PSERVER-STATS line:\n%s" % out)


def test_supervised_sole_trainer_relaunch_completes_the_job(
        tmp_path, capfd):
    """The nproc=1 corner of supervised trainer recovery: the death
    notification must NOT let the pserver declare the job done (empty
    live set) before the replacement boots — the respawn-aware evict
    parks the id and the eviction's own boundary readmits it, so the
    pserver outlives its only trainer's death and the relaunched
    process finishes every step."""
    from paddle_tpu.distributed.launch import launch_pserver

    marker = str(tmp_path / "crash_once")
    env = dict(os.environ)
    steps = 4
    env.update({
        "DIST_STEPS": str(steps),
        "DIST_STEP_SLEEP": "0.25",
        "DIST_CRASH_RANK": "0",
        "DIST_CRASH_AFTER_STEP": "1",
        "DIST_CRASH_ONCE": marker,
        "FLAGS_max_retry": "120",
        "JAX_PLATFORMS": "cpu",
    })
    rc = launch_pserver([_RUNNER], nproc=1, n_pservers=1, base_env=env,
                        sync=True, supervise=True, restart_backoff=0.2)
    out = capfd.readouterr().out
    assert rc == 0, out
    assert os.path.exists(marker), "the chaos crash never fired"
    assert "PSERVER EVICT trainer=0" in out, out
    assert "PSERVER READMIT trainer=0" in out, out
    losses = _trainer_losses(out, "trainer.0")
    assert len(losses) == steps and np.isfinite(losses).all(), losses


def test_sigkilled_trainer_is_evicted_and_survivor_finishes():
    """Acceptance: 2 sync trainers, trainer 1 SIGKILLs itself after step
    1; the pserver evicts it on the liveness deadline and trainer 0
    completes ALL its steps (the barrier un-hangs) with finite losses."""
    port = _free_port()
    eps = "127.0.0.1:%d" % port
    steps = 4
    common = {
        "PADDLE_PSERVER_EPS": eps,
        "PADDLE_TRAINERS": "2",
        "DIST_SYNC_MODE": "1",
        "DIST_STEPS": str(steps),
        "FLAGS_heartbeat_interval": "0.2",
        "FLAGS_eviction_deadline": "2.0",
    }
    ps = _spawn(dict(common, PADDLE_TRAINING_ROLE="PSERVER",
                     PADDLE_CURRENT_ENDPOINT=eps))
    victim = survivor = None
    try:
        _wait_port(port)
        survivor = _spawn(dict(common, PADDLE_TRAINING_ROLE="TRAINER",
                               PADDLE_TRAINER_ID="0"))
        victim = _spawn(dict(common, PADDLE_TRAINING_ROLE="TRAINER",
                             PADDLE_TRAINER_ID="1",
                             DIST_CRASH_RANK="1",
                             DIST_CRASH_AFTER_STEP="1"))
        losses, _ = _losses(survivor, timeout=180)
        assert len(losses) == steps
        assert np.isfinite(losses).all(), losses
        victim.wait(timeout=30)
        assert victim.returncode != 0  # it really died by SIGKILL
        ps_out, ps_err = ps.communicate(timeout=60)
        assert "PSERVER EVICT trainer=1" in ps_out, (ps_out, ps_err)
    finally:
        for p in (ps, victim, survivor):
            if p is not None and p.poll() is None:
                p.kill()


# ---------------------------------------------------------------------------
# incarnation fencing: minting, envelope, replay idempotency, restore fences
# ---------------------------------------------------------------------------

def test_incarnation_persists_and_increments_per_start(tmp_path):
    """Every pserver start in the same checkpoint home mints a HIGHER
    incarnation; without a durable home the numbers still differ."""
    ps1 = ParameterServer({}, {}, num_trainers=1,
                          checkpoint_dir=str(tmp_path), server_idx=0)
    ps2 = ParameterServer({}, {}, num_trainers=1,
                          checkpoint_dir=str(tmp_path), server_idx=0)
    assert ps2.incarnation == ps1.incarnation + 1
    # a different shard index has its own counter
    other = ParameterServer({}, {}, num_trainers=1,
                            checkpoint_dir=str(tmp_path), server_idx=1)
    assert other.incarnation == 1


def test_reply_envelope_carries_incarnation_to_client_registry():
    from paddle_tpu.distributed import rpc as rpc_mod

    ps = ParameterServer({}, {}, num_trainers=1, sync_mode=False)
    ps.incarnation = 41
    srv = VarServer("127.0.0.1:0", ps).start()
    try:
        cli = RPCClient(srv.endpoint, timeout=5, retries=3)
        cli.call("heartbeat", trainer_id=0)
        assert rpc_mod.incarnation_of(srv.endpoint) == 41
        before = rpc_mod.get_comm_stats()["pserver_restarts_seen"]
        ps.incarnation = 42  # the "restart"
        cli.call("heartbeat", trainer_id=0)
        assert rpc_mod.incarnation_of(srv.endpoint) == 42
        assert rpc_mod.get_comm_stats()["pserver_restarts_seen"] \
            == before + 1
        cli.close()
    finally:
        srv.shutdown()


def test_fenced_send_stream_counts_by_set_and_drops_folded_replays():
    """The replay-idempotency core: (step, seq_idx)-stamped buckets fold
    by SET (a duplicated bucket cannot advance the count), and once a
    step folded, replaying its whole stream is dropped at the fold fence
    instead of double-running the round."""
    ps = ParameterServer([None, None], {"g0": 0, "g1": 1}, num_trainers=1,
                         sync_mode=True)
    rounds = []
    ps._apply_shard = lambda idx, feed: rounds.append(
        {k: np.asarray(v).copy() for k, v in feed.items()})
    # bucket 0 of 2 arrives, then is REPLAYED (spurious): set semantics
    # keep the fold count at 1
    r = ps._h_send_bucket({"g0": np.full(2, 1.0)}, trainer_id=0,
                          seq_total=2, step=1, seq_idx=0)
    assert r == {"ok": True} and ps._round == 0
    r = ps._h_send_bucket({"g0": np.full(2, 1.0)}, trainer_id=0,
                          seq_total=2, step=1, seq_idx=0)
    assert r == {"ok": True} and ps._round == 0, "dup bucket advanced fold"
    # bucket 1 completes the set: the round runs exactly once
    r = ps._h_send_bucket({"g1": np.full(2, 5.0)}, trainer_id=0,
                          seq_total=2, step=1, seq_idx=1)
    assert r == {"ok": True} and ps._round == 1
    assert ps._folded_send[0] == 1
    # a full replay of the folded step (the restart path when the
    # snapshot already contained the round) is dropped, not re-run
    for i in range(2):
        r = ps._h_send_bucket({"g0": np.full(2, 9.0)}, trainer_id=0,
                              seq_total=2, step=1, seq_idx=i)
        assert r.get("dup_round"), r
    assert ps._round == 1 and len(rounds) == 2  # g0+g1 applied once each
    assert ps.counters["dup_round_drops"] == 2


def test_fenced_sparse_replay_dropped_after_fold():
    """A replayed sparse chunk stamped with an already-folded step must
    not leak into the next round's queue."""
    ps = ParameterServer(
        [None], {"g0": 0}, num_trainers=1, sync_mode=True,
        sparse_tables={"t0": {"tbl": np.zeros((4, 2), np.float32),
                              "lr": 0.1,
                              "opt": {"type": "sgd", "attrs": {}}}})
    ps._apply_shard = lambda idx, feed: None
    ps._h_send_sparse("t0", np.array([1]), np.ones((1, 2), np.float32),
                      trainer_id=0, step=1)
    ps._h_send_bucket({"g0": np.zeros(2)}, trainer_id=0, seq_total=1,
                      step=1, seq_idx=0)
    assert ps._round == 1 and not ps._pending_sparse
    # the fenced replay of step 1's sparse chunk after the fold
    r = ps._h_send_sparse("t0", np.array([1]), np.ones((1, 2), np.float32),
                          trainer_id=0, step=1)
    assert r.get("dup_round"), r
    assert not ps._pending_sparse, "replayed rows leaked into next round"


def test_send_fold_waits_for_declared_sparse_chunks():
    """A crash between the sparse acks and the dense folds re-delivers
    only the (unacked) dense buckets via RPC retries: the restarted
    server must NOT run the round without the sparse rows the dead
    incarnation had only queued in memory — the dense fold refuses
    (need_sparse) until the fenced replay re-queues every declared
    chunk, then applies the round exactly once WITH them."""
    ps = ParameterServer(
        [None], {"g0": 0}, num_trainers=1, sync_mode=True,
        sparse_tables={"t0": {"tbl": np.zeros((4, 2), np.float32),
                              "lr": 0.1,
                              "opt": {"type": "sgd", "attrs": {}}}})
    applied = []
    ps._apply_shard = lambda idx, feed: applied.append(
        np.asarray(feed["g0"]).copy())
    # the retried dense bucket arrives first (fresh post-restart server,
    # sparse chunk lost with the old incarnation's memory)
    r = ps._h_send_bucket({"g0": np.full(2, 1.0)}, trainer_id=0,
                          seq_total=1, step=1, seq_idx=0,
                          sparse_tables=["t0"])
    assert r.get("need_sparse") == ["t0"], r
    assert ps._round == 0 and not applied, \
        "round ran without its declared sparse rows"
    # the fenced replay ships sparse FIRST, then the dense buckets
    ps._h_send_sparse("t0", np.array([1]), np.ones((1, 2), np.float32),
                      trainer_id=0, step=1)
    r = ps._h_send_bucket({"g0": np.full(2, 1.0)}, trainer_id=0,
                          seq_total=1, step=1, seq_idx=0,
                          sparse_tables=["t0"])
    assert r == {"ok": True} and ps._round == 1
    assert len(applied) == 1
    np.testing.assert_allclose(
        ps.sparse_tables["t0"]["tbl"][1], np.full(2, -0.1), atol=1e-6)


def test_restored_server_serves_params_and_fences_folded_rounds(tmp_path):
    """The restart seam end-to-end, in-process: a sync server folds a
    fenced round and checkpoints; the RESTORED server (a) serves params
    immediately (params_ready — a restart during the fetch phase must
    not deadlock), (b) restores the fold fence so a replay of the
    checkpointed round is dropped, and (c) re-assembles a round the
    snapshot never saw."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=1, sync_mode=True,
                         checkpoint_dir=str(tmp_path), server_idx=0,
                         checkpoint_every=1)
    applied = []
    ps._apply_shard = lambda idx, feed: applied.append(
        np.asarray(feed["g0"]).copy())
    ps.scope.set("p.block0", np.zeros(2, np.float32))
    ps._h_send_bucket({"g0": np.full(2, 3.0)}, trainer_id=0, seq_total=1,
                      step=1, seq_idx=0)
    assert ps._round == 1
    # the checkpoint writer runs on a background thread: wait for the
    # manifest (existence is the fence, not a fixed sleep)
    deadline = time.monotonic() + 30
    mpath = tmp_path / "pserver_0.manifest.json"
    while time.monotonic() < deadline and not (
            mpath.exists() and json.loads(mpath.read_text())["round"] == 1):
        time.sleep(0.05)
    assert mpath.exists()

    ps2 = ParameterServer([None], {"g0": 0}, num_trainers=1, sync_mode=True,
                          checkpoint_dir=str(tmp_path), server_idx=0)
    applied2 = []
    ps2._apply_shard = lambda idx, feed: applied2.append(
        np.asarray(feed["g0"]).copy())
    assert ps2.load_checkpoint() == 1
    assert ps2.incarnation > ps.incarnation
    assert ps2._params_ready is True, \
        "restored sync server must serve the checkpointed round's params"
    assert ps2._folded_send == {0: 1}
    # (b) replaying the checkpointed round: dropped
    r = ps2._h_send_bucket({"g0": np.full(2, 3.0)}, trainer_id=0,
                           seq_total=1, step=1, seq_idx=0)
    assert r.get("dup_round") and ps2._round == 1 and not applied2
    # (c) the NEXT round (which the snapshot never saw) re-assembles
    r = ps2._h_send_bucket({"g0": np.full(2, 7.0)}, trainer_id=0,
                           seq_total=1, step=2, seq_idx=0)
    assert r == {"ok": True} and ps2._round == 2
    np.testing.assert_array_equal(applied2[0], np.full(2, 7.0))


def test_send_fence_gap_one_round_tolerated_wider_gap_fails():
    """The trainer replays only its CURRENT round, so a restore behind
    the stream loses the rounds in between.  A ONE-round gap (the kill
    raced the async checkpoint write) proceeds loudly — counted, never
    silent; a wider gap (checkpoint_every > 1 discarding rounds on
    every restore) must fail the job instead of quietly training past
    several lost updates."""
    ps = ParameterServer([None, None], {"g0": 0, "g1": 1}, num_trainers=1,
                         sync_mode=True)
    applied = []
    ps._apply_shard = lambda idx, feed: applied.append(dict(feed))
    # restored fence: the snapshot last folded step 1 for trainer 0
    ps._folded_send[0] = 1
    # step 3 arrives over TWO buckets (step 2 unrecoverable): tolerated,
    # and counted ONCE per lost round, not once per arriving bucket
    r = ps._h_send_bucket({"g0": np.full(1, 3.0)}, trainer_id=0,
                          seq_total=2, step=3, seq_idx=0)
    assert r == {"ok": True} and ps._round == 0
    r = ps._h_send_bucket({"g1": np.full(1, 3.0)}, trainer_id=0,
                          seq_total=2, step=3, seq_idx=1)
    assert r == {"ok": True} and ps._round == 1
    assert ps.counters["lost_rounds"] == 1
    # step 6 arrives next (steps 4 AND 5 lost): refuse loudly.  handle()
    # wraps the raise into the error envelope the client re-raises from.
    r = ps.handle("send_bucket", blocks={"g0": np.full(2, 9.0)},
                  trainer_id=0, seq_total=1, step=6, seq_idx=0)
    assert "incarnation fence gap" in r.get("__error__", ""), r
    assert ps._round == 1 and len(applied) == 2, \
        "a refused gap must not fold or run a round"


def test_restored_server_remembers_departed_trainers(tmp_path):
    """A restored sync server must not rebuild its live set around
    ghosts it evicted before the restart — their folds would never
    arrive and every restored barrier would hang.  The departed sets
    ride the snapshot; register still readmits."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=2, sync_mode=True,
                         checkpoint_dir=str(tmp_path), server_idx=0,
                         checkpoint_every=1)
    ps._apply_shard = lambda idx, feed: None
    with ps._cv:
        ps._evict_locked(1, "test")
    # survivor's round runs and checkpoints (manifest = the fence)
    ps._h_send_bucket({"g0": np.ones(2)}, trainer_id=0, seq_total=1,
                      step=1, seq_idx=0)
    mpath = tmp_path / "pserver_0.manifest.json"
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not mpath.exists():
        time.sleep(0.05)
    assert mpath.exists()
    ps2 = ParameterServer([None], {"g0": 0}, num_trainers=2, sync_mode=True,
                          checkpoint_dir=str(tmp_path), server_idx=0)
    ps2._apply_shard = lambda idx, feed: None
    assert ps2.load_checkpoint() == 1
    assert ps2._live == {0} and 1 in ps2._evicted, \
        "restored server forgot the eviction"
    # the survivor's next round completes ALONE on the restored server
    r = ps2._h_send_bucket({"g0": np.ones(2)}, trainer_id=0, seq_total=1,
                           step=2, seq_idx=0)
    # the restored eviction re-marks the membership change: the reply
    # carries the (snapshot-restored, re-minted) plan epoch
    assert r["ok"] is True and "evicted" not in r and ps2._round == 2
    # and the ghost can still come back through register
    assert ps2._h_register(trainer_id=1)["ok"]
    assert ps2._live == {0, 1}


def test_legacy_bare_array_checkpoint_upgrades_and_rewrites_manifest(
        tmp_path):
    """Satellite: a legacy checkpoint (bare sparse table arrays, no
    manifest) loads, upgrades the in-memory layout, and rewrites BOTH
    files in the modern format — snapshot with dict-shaped sparse state
    plus a crc-carrying manifest that verifies."""
    import pickle
    import zlib

    legacy = {
        "round": 4,
        "vars": {"w.block0": np.arange(3, dtype=np.float32)},
        "sparse": {"t0": np.full((4, 2), 2.0, np.float32)},  # bare array
    }
    path = tmp_path / "pserver_0.ckpt"
    path.write_bytes(pickle.dumps(legacy, protocol=pickle.HIGHEST_PROTOCOL))
    ps = ParameterServer(
        {}, {}, num_trainers=1, sync_mode=False,
        checkpoint_dir=str(tmp_path), server_idx=0,
        sparse_tables={"t0": {"tbl": np.zeros((4, 2), np.float32),
                              "lr": 0.1,
                              "opt": {"type": "sgd", "attrs": {}}}})
    assert ps.load_checkpoint() == 4
    np.testing.assert_array_equal(
        np.asarray(ps.scope.find_var("w.block0")),
        np.arange(3, dtype=np.float32))
    np.testing.assert_array_equal(ps.sparse_tables["t0"]["tbl"],
                                  np.full((4, 2), 2.0, np.float32))
    # the rewrite landed a modern crc manifest over a modern snapshot
    mpath = tmp_path / "pserver_0.manifest.json"
    assert mpath.exists(), "upgrade did not write a manifest"
    manifest = json.loads(mpath.read_text())
    payload = path.read_bytes()
    assert manifest["round"] == 4
    assert manifest["nbytes"] == len(payload)
    assert manifest["crc32"] == (zlib.crc32(payload) & 0xFFFFFFFF)
    upgraded = pickle.loads(payload)
    assert isinstance(upgraded["sparse"]["t0"], dict)
    np.testing.assert_array_equal(upgraded["sparse"]["t0"]["tbl"],
                                  np.full((4, 2), 2.0, np.float32))
    # and a THIRD server restores cleanly from the rewritten pair
    ps3 = ParameterServer(
        {}, {}, num_trainers=1, sync_mode=False,
        checkpoint_dir=str(tmp_path), server_idx=0,
        sparse_tables={"t0": {"tbl": np.zeros((4, 2), np.float32),
                              "lr": 0.1,
                              "opt": {"type": "sgd", "attrs": {}}}})
    assert ps3.load_checkpoint() == 4


# ---------------------------------------------------------------------------
# elastic trainer rejoin (register verb)
# ---------------------------------------------------------------------------

def test_register_readmits_evicted_trainer_and_barrier_totals_grow():
    """The rejoin core: an evicted id re-registers, is readmitted at the
    round boundary, and the NEXT round's barrier denominator includes it
    — the survivor's fold alone no longer runs the round."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=2, sync_mode=True)
    applied = []
    ps._apply_shard = lambda idx, feed: applied.append(
        np.asarray(feed["g0"]).copy())
    srv = VarServer("127.0.0.1:0", ps).start()
    try:
        cli = RPCClient(srv.endpoint, timeout=30, retries=3)
        with ps._cv:
            ps._evict_locked(1, "test")
        assert ps._live == {0}
        # round boundary (nothing pending): register readmits immediately
        r = cli.register(trainer_id=1)
        assert r["ok"] and r["incarnation"] == ps.incarnation
        assert ps._live == {0, 1} and 1 not in ps._evicted
        assert ps.counters["readmissions"] == 1
        # barrier totals reflect the rejoin: the survivor's fold no
        # longer completes the round by itself — it PARKS waiting on the
        # readmitted trainer...
        survivor = []
        th0 = threading.Thread(target=lambda: survivor.append(
            cli.call("send_bucket", blocks={"g0": np.full(2, 3.0)},
                     trainer_id=0, seq_total=1, step=1, seq_idx=0)),
            daemon=True)
        th0.start()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and 0 not in ps._send_barriers:
            time.sleep(0.01)
        assert 0 in ps._send_barriers and ps._round == 0, \
            "round ran without the readmitted trainer"
        # ...until the joiner's stream folds too (its step tokens restart
        # at 1 — the admission cleared any stale fold fence)
        done = []
        cli1 = RPCClient(srv.endpoint, timeout=30, retries=3)
        th = threading.Thread(target=lambda: done.append(
            cli1.call("send_bucket", blocks={"g0": np.full(2, 5.0)},
                     trainer_id=1, seq_total=1, step=1, seq_idx=0)),
            daemon=True)
        th.start()
        th.join(timeout=10)
        th0.join(timeout=10)
        # eviction + readmission each minted a plan epoch; the post-
        # round replies carry the latest
        assert done and done[0] == {"ok": True, "pepoch": 2}
        assert survivor and survivor[0] == {"ok": True, "pepoch": 2}
        assert ps._round == 1
        cli1.close()
        assert len(applied) == 1
        np.testing.assert_array_equal(applied[0], np.full(2, 8.0))
        cli.close()
    finally:
        srv.shutdown()


def test_register_midround_waits_for_the_boundary():
    """Admission is a FENCE on the round boundary: a register arriving
    while a round is being assembled parks until that round completes,
    so the in-flight denominator never changes under the survivors."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=2, sync_mode=True)
    ps._apply_shard = lambda idx, feed: None
    srv = VarServer("127.0.0.1:0", ps).start()
    try:
        cli = RPCClient(srv.endpoint, timeout=30, retries=3)
        with ps._cv:
            ps._evict_locked(1, "test")
        # survivor starts assembling a 2-bucket round: mid-round now
        cli.call("send_bucket", blocks={"g0": np.full(2, 1.0)},
                 trainer_id=0, seq_total=2, step=1, seq_idx=0)
        got = []
        cli2 = RPCClient(srv.endpoint, timeout=30, retries=3)
        th = threading.Thread(
            target=lambda: got.append(cli2.register(trainer_id=1)),
            daemon=True)
        th.start()
        # fence, not delay: the register is parked in _pending_joins
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and 1 not in ps._pending_joins:
            time.sleep(0.01)
        assert 1 in ps._pending_joins, "register was not queued mid-round"
        assert 1 not in ps._live
        # the round completes -> the joiner is admitted at its boundary
        cli.call("send_bucket", blocks={"g0": np.full(2, 1.0)},
                 trainer_id=0, seq_total=2, step=1, seq_idx=1)
        th.join(timeout=10)
        assert got and got[0]["ok"] and got[0]["round"] == 1
        assert ps._live == {0, 1}
        cli.close()
        cli2.close()
    finally:
        srv.shutdown()


def test_respawn_evict_of_sole_trainer_keeps_the_job_alive():
    """A supervised child's death report carries respawn=True: evicting
    the SOLE trainer must park + readmit the id instead of declaring
    the job done — the pserver has to outlive the boot window of the
    replacement the supervisor is about to spawn."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=1, sync_mode=True)
    applied = []
    ps._apply_shard = lambda idx, feed: applied.append(
        np.asarray(feed["g0"]).copy())
    r = ps._h_evict(trainer_id=0, respawn=True)
    assert r["ok"] and r["live"] == 1
    assert not ps._done.is_set(), \
        "job declared done under the booting replacement"
    assert ps._live == {0} and ps.counters["readmissions"] == 1
    # the replacement arrives: registers (fresh stream) and trains
    assert ps._h_register(trainer_id=0)["ok"]
    ps._h_send_bucket({"g0": np.full(2, 2.0)}, trainer_id=0, seq_total=1,
                      step=1, seq_idx=0)
    assert ps._round == 1 and len(applied) == 1
    ps._h_complete(trainer_id=0)
    assert ps._done.is_set()
    # contrast: an UNSUPERVISED sole-trainer death still ends the job
    ps2 = ParameterServer([None], {"g0": 0}, num_trainers=1,
                          sync_mode=True)
    ps2._h_evict(trainer_id=0)
    assert ps2._done.is_set()
    # async mode parks + readmits too (no barriers, so the boundary
    # admits immediately) — the async pserver must equally outlive its
    # sole trainer's supervised death
    ps3 = ParameterServer([None], {"g0": 0}, num_trainers=1,
                          sync_mode=False)
    ps3._h_evict(trainer_id=0, respawn=True)
    assert not ps3._done.is_set() and ps3._live == {0}


def test_register_rejection_is_terminal_for_the_trainer():
    """A joiner parked in `register` while the job completes gets
    ok:False back — and the trainer-side handshake must treat that as
    TERMINAL: with the live set empty, its sends would each run a
    "round" alone, silently training the final checkpointed params."""
    from paddle_tpu import distributed

    ps = ParameterServer([None], {"g0": 0}, num_trainers=2, sync_mode=True)
    ps._apply_shard = lambda idx, feed: None
    srv = VarServer("127.0.0.1:0", ps).start()
    ep = srv.endpoint
    key = (ep, 1)
    try:
        with ps._cv:
            ps._evict_locked(1, "test")
        cli = RPCClient(ep, timeout=30, retries=3)
        # survivor mid-round (1 of 2 buckets): the rejoin must park
        cli.call("send_bucket", blocks={"g0": np.full(2, 1.0)},
                 trainer_id=0, seq_total=2, step=1, seq_idx=0)
        err = []

        def join():
            try:
                distributed._note_endpoint(ep, 1)
                err.append(None)
            except RuntimeError as e:
                err.append(e)

        th = threading.Thread(target=join, daemon=True)
        th.start()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and 1 not in ps._pending_joins:
            time.sleep(0.01)
        assert 1 in ps._pending_joins, "register was not queued mid-round"
        # the survivor departs mid-round: job done, joiner rejected
        cli.call("complete", trainer_id=0)
        th.join(timeout=10)
        assert err and isinstance(err[0], RuntimeError), \
            "rejected register must raise, not fall through to training"
        assert "already completed" in str(err[0])
        cli.close()
    finally:
        distributed._active_endpoints.discard(key)
        with RPCClient._lock:
            RPCClient._instances.pop(ep, None)
        srv.shutdown()


def test_eviction_of_sole_midround_contributor_restores_the_boundary():
    """Regression: evicting the only trainer that had contributed grads
    must leave NO empty per-grad dicts behind in _pending — a leftover
    {} kept _mid_round_locked() True forever, so a rejoining trainer
    could never be admitted and the job was wrongly declared done."""
    ps = ParameterServer([None, None], {"g0": 0, "g1": 1}, num_trainers=2,
                         sync_mode=True)
    ps._apply_shard = lambda idx, feed: None
    # trainer 1 ships bucket 0 of 2 (mid-round now) and dies
    ps._h_send_bucket({"g0": np.ones(2)}, trainer_id=1, seq_total=2,
                      step=1, seq_idx=0)
    assert ps._mid_round_locked()
    with ps._cv:
        ps._evict_locked(1, "test")
    assert not ps._mid_round_locked(), \
        "empty pending dict kept the server mid-round forever"
    assert ps._at_boundary_locked()
    # a rejoin is admitted immediately at the restored boundary
    assert ps._h_register(trainer_id=1)["ok"]
    assert ps._live == {0, 1}


def test_register_waits_out_pending_fetch_barrier():
    """Admission must respect the FETCH phase too: a join admitted while
    the served round's fetch barrier still pends would grow the fetch
    denominator under the survivors — the stale entries could later
    complete with the joiner's first fetch and flip params_ready off
    while survivors still hold un-served gets.  The join parks until the
    fetch drains."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=2, sync_mode=True)
    ps._apply_shard = lambda idx, feed: None
    # post-round state: params served, trainer 0 folded its fetch,
    # trainer 1 still fetching
    ps._params_ready = True
    ps._fetch_barriers = {0}
    srv = VarServer("127.0.0.1:0", ps).start()
    try:
        cli = RPCClient(srv.endpoint, timeout=30, retries=3)
        got = []
        th = threading.Thread(
            target=lambda: got.append(cli.register(trainer_id=2)),
            daemon=True)
        th.start()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and 2 not in ps._pending_joins:
            time.sleep(0.01)
        assert 2 in ps._pending_joins and 2 not in ps._live, \
            "join admitted while the fetch barrier still pends"
        # trainer 1 folds its fetch: the barrier drains -> boundary ->
        # the joiner is admitted and params_ready was reset exactly once
        cli2 = RPCClient(srv.endpoint, timeout=30, retries=3)
        assert cli2.call("barrier", kind="fetch", trainer_id=1)["ok"]
        th.join(timeout=10)
        assert got and got[0]["ok"]
        assert ps._live == {0, 1, 2}
        assert ps._params_ready is False and not ps._fetch_barriers
        cli.close()
        cli2.close()
    finally:
        srv.shutdown()


def test_register_of_live_id_resets_its_partial_round_state():
    """A fast relaunch (died and came back before eviction noticed): the
    fresh incarnation's register drops the ghost's partial stream and
    fold fences so its restarted step tokens count from scratch."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=2, sync_mode=True)
    ps._apply_shard = lambda idx, feed: None
    # ghost shipped bucket 0 of 2 at step 5, then died silently
    ps._h_send_bucket({"g0": np.ones(2)}, trainer_id=1, seq_total=2,
                      step=5, seq_idx=0)
    ps._folded_send[1] = 4
    assert ps._send_seen.get(1) == {0}
    r = ps._h_register(trainer_id=1)
    assert r["ok"]
    assert 1 not in ps._send_seen and 1 not in ps._send_step
    assert 1 not in ps._folded_send, "stale fold fence would drop the " \
        "fresh process's restarted stream"
    assert all(1 not in per for per in ps._pending.values())


# ---------------------------------------------------------------------------
# flags: liveness-pair validation (satellite)
# ---------------------------------------------------------------------------

def test_eviction_deadline_clamped_when_not_above_heartbeat(capsys):
    from paddle_tpu import flags

    orig_hb = flags.get_flag("heartbeat_interval")
    orig_ev = flags.get_flag("eviction_deadline")
    try:
        flags.set_flags({"heartbeat_interval": 5.0,
                         "eviction_deadline": 2.0})
        assert flags.get_flag("eviction_deadline") == 15.0, \
            "self-evicting pair must clamp to 3x the interval"
        err = capsys.readouterr().err
        assert "clamping eviction_deadline" in err
        # a sane pair passes through untouched
        flags.set_flags({"heartbeat_interval": 1.0,
                         "eviction_deadline": 30.0})
        assert flags.get_flag("eviction_deadline") == 30.0
        # heartbeats disabled: no eviction, nothing to validate
        flags.set_flags({"heartbeat_interval": 0.0,
                         "eviction_deadline": 0.5})
        assert flags.get_flag("eviction_deadline") == 0.5
    finally:
        flags.set_flags({"heartbeat_interval": orig_hb,
                         "eviction_deadline": orig_ev})


# ---------------------------------------------------------------------------
# launch.py: supervisor + resource reaping (satellites)
# ---------------------------------------------------------------------------

def test_restart_policy_budget_and_backoff():
    from paddle_tpu.distributed.launch import _RestartPolicy

    pol = _RestartPolicy(max_restarts=2, window_s=60.0, backoff_s=0.5)
    assert pol.next_delay() == 0.5
    assert pol.next_delay() == 1.0  # exponential
    assert pol.next_delay() is None, "budget must exhaust"


def test_cluster_reaps_pipes_and_threads_on_kill():
    """Satellite: kill() must leave no live pump threads and no open
    child stdout pipes, so repeated chaos tests don't leak fds."""
    from paddle_tpu.distributed.launch import _Cluster

    cluster = _Cluster()
    env = dict(os.environ)
    for i in range(3):
        cluster.spawn("sleeper.%d" % i,
                      [sys.executable, "-c", "import time; time.sleep(60)"],
                      env)
    cluster.kill()
    for _tag, p, t in cluster.procs:
        assert p.poll() is not None
        assert not t.is_alive(), "pump thread leaked past kill()"
        assert p.stdout.closed, "child stdout pipe leaked past kill()"


def test_cluster_wait_reaps_pipes_on_clean_exit():
    from paddle_tpu.distributed.launch import _Cluster

    cluster = _Cluster()
    cluster.spawn("ok", [sys.executable, "-c", "print('fine')"],
                  dict(os.environ))
    assert cluster.wait() == 0
    for _tag, p, t in cluster.procs:
        t.join(timeout=5)
        assert not t.is_alive()
        assert p.stdout.closed


def test_supervisor_respawns_until_budget_then_fails():
    """A supervised child that keeps dying is restarted with backoff
    until the budget runs out; the FINAL death is a real failure."""
    from paddle_tpu.distributed.launch import _Cluster, _RestartPolicy

    cluster = _Cluster()
    env = dict(os.environ)
    cmd = [sys.executable, "-c", "import sys; sys.exit(3)"]
    cluster.supervise("flaky", cmd, env,
                      _RestartPolicy(max_restarts=2, window_s=60.0,
                                     backoff_s=0.05))
    cluster.spawn("flaky", cmd, env)
    rc = cluster.wait()
    assert rc == 3, "budget-exhausted death must surface as failure"
    assert cluster.restarts["flaky"] == 2
    # 3 incarnations total: original + 2 respawns, all reaped
    assert len([1 for t, _, _ in cluster.procs if t == "flaky"]) == 3


def test_supervisor_respawn_recovers_crash_once_child(tmp_path):
    """The self-healing happy path: a child that dies once (marker file
    = the fence) is respawned and its second incarnation exits clean —
    the cluster reports success and the dead Popen is excused."""
    from paddle_tpu.distributed.launch import _Cluster, _RestartPolicy

    marker = str(tmp_path / "crashed_once")
    code = ("import os, sys\n"
            "m = %r\n"
            "if os.path.exists(m):\n"
            "    sys.exit(0)\n"
            "open(m, 'w').close()\n"
            "sys.exit(7)\n" % marker)
    cluster = _Cluster()
    env = dict(os.environ)
    cmd = [sys.executable, "-c", code]
    cluster.supervise("once", cmd, env,
                      _RestartPolicy(max_restarts=3, backoff_s=0.05))
    cluster.spawn("once", cmd, env)
    assert cluster.wait() == 0
    assert cluster.restarts["once"] == 1
    assert os.path.exists(marker)


def test_supervisor_on_respawn_hook_can_cancel():
    from paddle_tpu.distributed.launch import _Cluster, _RestartPolicy

    cluster = _Cluster()
    env = dict(os.environ)
    cmd = [sys.executable, "-c", "import sys; sys.exit(9)"]
    seen = []

    def hook(tag):
        seen.append(tag)
        return False  # "the job already completed without it"

    cluster.on_respawn = hook
    cluster.supervise("late", cmd, env, _RestartPolicy(backoff_s=0.05))
    cluster.spawn("late", cmd, env)
    assert cluster.wait() == 0, "cancelled respawn must not fail the run"
    assert seen == ["late"]
    assert cluster.restarts.get("late") is None


# ---------------------------------------------------------------------------
# durable async sparse: write-ahead journal, fenced replay, bounded staleness
# ---------------------------------------------------------------------------

def _async_sparse_ps(ckpt_dir=None, num_trainers=1, staleness_bound=0,
                     **kw):
    ps = ParameterServer(
        [None], {"g0": 0}, num_trainers=num_trainers, sync_mode=False,
        checkpoint_dir=ckpt_dir, server_idx=0,
        staleness_bound=staleness_bound,
        sparse_tables={"t0": {"tbl": np.zeros((8, 4), np.float32),
                              "lr": 0.1,
                              "opt": {"type": "sgd", "attrs": {}}}}, **kw)
    ps._apply_shard = lambda idx, feed: None
    return ps


def _chunk(i):
    ids = np.array([i % 8, (i + 3) % 8], np.int64)
    rows = np.full((2, 4), float(i + 1), np.float32)
    return ids, rows


def test_async_journal_replay_restores_exact_table(tmp_path):
    """THE async gap, closed: updates applied after the last snapshot
    live in the fsync'd journal — a restarted incarnation replays them
    and its table is BIT-IDENTICAL to the dead server's.  The restored
    seq fence then drops a re-shipped (at-least-once) chunk instead of
    double-applying it."""
    ps = _async_sparse_ps(str(tmp_path))
    for i in range(2):
        ids, rows = _chunk(i)
        r = ps._h_send_sparse("t0", ids, rows, trainer_id=0, seq=i + 1)
        assert r == {"ok": True, "acked": i + 1}
    assert ps.save_checkpoint()  # snapshot (rotates the journal)
    for i in range(2, 5):
        ids, rows = _chunk(i)
        ps._h_send_sparse("t0", ids, rows, trainer_id=0, seq=i + 1)
    want = np.array(ps.sparse_tables["t0"]["tbl"])

    ps2 = _async_sparse_ps(str(tmp_path))
    assert ps2.load_checkpoint() is not None
    assert ps2.counters["journal_replayed"] == 3, ps2.counters
    np.testing.assert_array_equal(ps2.sparse_tables["t0"]["tbl"], want)
    assert ps2._sparse_fence == {(0, "t0"): 5}
    # at-least-once re-delivery of an already-durable chunk: dropped
    ids, rows = _chunk(4)
    r = ps2._h_send_sparse("t0", ids, rows, trainer_id=0, seq=5)
    assert r == {"ok": True, "dup": True, "acked": 5}
    assert ps2.counters["dedup_drops"] == 1
    np.testing.assert_array_equal(ps2.sparse_tables["t0"]["tbl"], want)
    # the NEXT chunk (never applied before the kill) applies normally
    ids, rows = _chunk(5)
    assert ps2._h_send_sparse("t0", ids, rows, trainer_id=0,
                              seq=6)["acked"] == 6
    assert not np.array_equal(ps2.sparse_tables["t0"]["tbl"], want)


def test_async_journal_cold_start_replays_full_history(tmp_path):
    """No snapshot ever landed: the journal (never rotated without one)
    holds the whole applied stream — replaying from segment 0 is a full
    recovery, not a cold loss."""
    ps = _async_sparse_ps(str(tmp_path))
    for i in range(3):
        ids, rows = _chunk(i)
        ps._h_send_sparse("t0", ids, rows, trainer_id=0, seq=i + 1)
    want = np.array(ps.sparse_tables["t0"]["tbl"])
    ps2 = _async_sparse_ps(str(tmp_path))
    assert ps2.load_checkpoint() is not None  # journal-only restore
    np.testing.assert_array_equal(ps2.sparse_tables["t0"]["tbl"], want)
    assert ps2.counters["journal_replayed"] == 3


def test_async_journal_truncated_tail_skipped_cold(tmp_path):
    """A kill mid-append leaves a truncated/corrupt tail record: restore
    applies every COMPLETE record, skips the tail with a counter (like a
    corrupt snapshot), and never crash-loops.  The unacked tail chunk is
    the client's to re-ship."""
    ps = _async_sparse_ps(str(tmp_path))
    for i in range(3):
        ids, rows = _chunk(i)
        ps._h_send_sparse("t0", ids, rows, trainer_id=0, seq=i + 1)
    seg = tmp_path / ("pserver_0.journal.seg%06d" % 0)
    raw = seg.read_bytes()
    seg.write_bytes(raw[:-7])  # tear the last record mid-payload

    ps_mid = _async_sparse_ps(str(tmp_path))
    for i in range(2):  # expected state: first two chunks only
        ids, rows = _chunk(i)
        ps_mid._h_send_sparse("t0", ids, rows, trainer_id=0, seq=i + 1)

    ps2 = _async_sparse_ps(str(tmp_path))
    assert ps2.load_checkpoint() is not None
    assert ps2.counters["journal_replayed"] == 2
    assert ps2.counters["journal_tail_skips"] == 1
    np.testing.assert_array_equal(ps2.sparse_tables["t0"]["tbl"],
                                  ps_mid.sparse_tables["t0"]["tbl"])
    # the fence sits at the last DURABLE chunk, so the client's re-ship
    # of the torn one applies (monotonic fence: seq 3 > 2)
    assert ps2._sparse_fence == {(0, "t0"): 2}
    ids, rows = _chunk(2)
    assert ps2._h_send_sparse("t0", ids, rows, trainer_id=0,
                              seq=3)["acked"] == 3


def test_async_garbage_journal_segment_skipped_cold(tmp_path):
    """A fully-garbage segment (bad crc from byte 0) must not crash the
    restore — zero records replay, the skip is counted."""
    ps = _async_sparse_ps(str(tmp_path))
    ids, rows = _chunk(0)
    ps._h_send_sparse("t0", ids, rows, trainer_id=0, seq=1)
    seg = tmp_path / ("pserver_0.journal.seg%06d" % 0)
    seg.write_bytes(b"\xff" * len(seg.read_bytes()))
    ps2 = _async_sparse_ps(str(tmp_path))
    assert ps2.load_checkpoint() is None  # nothing usable: cold start
    assert ps2.counters["journal_tail_skips"] == 1
    np.testing.assert_array_equal(ps2.sparse_tables["t0"]["tbl"],
                                  np.zeros((8, 4), np.float32))


def test_async_snapshot_deletes_covered_journal_segments(tmp_path):
    """Rotation bounds the journal: once a snapshot lands, the segments
    it contains are deleted; the restore path only ever replays
    journal-after-snapshot."""
    ps = _async_sparse_ps(str(tmp_path))
    for i in range(2):
        ids, rows = _chunk(i)
        ps._h_send_sparse("t0", ids, rows, trainer_id=0, seq=i + 1)
    assert ps.save_checkpoint()
    segs = [p.name for p in tmp_path.iterdir() if ".journal." in p.name]
    assert segs == [], "covered segments survived the snapshot: %s" % segs
    ids, rows = _chunk(2)
    ps._h_send_sparse("t0", ids, rows, trainer_id=0, seq=3)
    ps2 = _async_sparse_ps(str(tmp_path))
    assert ps2.load_checkpoint() is not None
    assert ps2.counters["journal_replayed"] == 1  # only the post-snap one
    np.testing.assert_array_equal(ps2.sparse_tables["t0"]["tbl"],
                                  ps.sparse_tables["t0"]["tbl"])


def test_async_corrupt_snapshot_quarantines_orphaned_journal(tmp_path):
    """Regression (review finding): a torn SNAPSHOT orphans its journal
    — the segments hold deltas whose base is gone.  The cold start must
    quarantine them (remove + reseed the writer past their numbering),
    or the next lineage would append into / replay dead-lineage records
    on top of fresh state."""
    ps = _async_sparse_ps(str(tmp_path))
    ids, rows = _chunk(0)
    ps._h_send_sparse("t0", ids, rows, trainer_id=0, seq=1)
    assert ps.save_checkpoint()  # rotates to seg 1, deletes seg 0
    ids, rows = _chunk(1)
    ps._h_send_sparse("t0", ids, rows, trainer_id=0, seq=2)  # -> seg 1
    # tear the snapshot (crash mid-write)
    snap = tmp_path / "pserver_0.ckpt"
    snap.write_bytes(snap.read_bytes()[: 40])

    ps2 = _async_sparse_ps(str(tmp_path))
    assert ps2.load_checkpoint() is None  # cold start
    assert not [p for p in tmp_path.iterdir()
                if ".journal." in p.name], \
        "orphaned dead-lineage segments survived the cold start"
    # run_pserver's birth snapshot replaces the torn one after a cold
    # start (journal-armed servers always persist their base)
    assert ps2.save_checkpoint()
    # the new lineage is self-consistent: fresh updates + a restart
    # see ONLY the new lineage (no dead-lineage mixing)
    ids, rows = _chunk(2)
    assert ps2._h_send_sparse("t0", ids, rows, trainer_id=0,
                              seq=1)["acked"] == 1
    want = np.array(ps2.sparse_tables["t0"]["tbl"])
    ps3 = _async_sparse_ps(str(tmp_path))
    assert ps3.load_checkpoint() is not None
    np.testing.assert_array_equal(ps3.sparse_tables["t0"]["tbl"], want)
    assert ps3._sparse_fence == {(0, "t0"): 1}


def test_async_journal_seg_reseeds_past_snapshot_after_restore(tmp_path):
    """Regression (review finding): a restore whose snapshot covered —
    and deleted — every journal segment must reseed the WRITER past the
    snapshot's replay-from marker.  Resetting to segment 0 would park
    post-restore appends BELOW the marker, and a second restart would
    skip them — silently losing acked, fsync'd updates."""
    ps = _async_sparse_ps(str(tmp_path))
    ids, rows = _chunk(0)
    ps._h_send_sparse("t0", ids, rows, trainer_id=0, seq=1)
    assert ps.save_checkpoint()  # covers + deletes segment 0

    ps2 = _async_sparse_ps(str(tmp_path))
    assert ps2.load_checkpoint() is not None
    # the writer must sit at/above the snapshot's replay-from marker
    ids, rows = _chunk(1)
    ps2._h_send_sparse("t0", ids, rows, trainer_id=0, seq=2)
    want = np.array(ps2.sparse_tables["t0"]["tbl"])

    ps3 = _async_sparse_ps(str(tmp_path))
    assert ps3.load_checkpoint() is not None
    assert ps3.counters["journal_replayed"] == 1, \
        "post-restore append landed below the replay-from marker"
    np.testing.assert_array_equal(ps3.sparse_tables["t0"]["tbl"], want)
    assert ps3._sparse_fence == {(0, "t0"): 2}


def test_async_dense_bucket_fence_out_of_order_and_dup(tmp_path):
    """Async dense buckets ride the pipelined window (out-of-order
    arrivals are legal): the contiguous fence + ahead-set applies each
    aseq exactly once, dedupes re-delivery, and journal replay restores
    the applied stream bit for bit."""
    ps = _async_sparse_ps(str(tmp_path))
    applied = []
    ps._apply_async_send_locked = \
        lambda name, value, _a=applied: _a.append(
            (name, float(np.asarray(value).reshape(-1)[0])))
    r = ps._h_send_bucket({"g0": np.full(2, 2.0)}, trainer_id=0, aseq=2)
    # gap: fence waits for aseq 1 (dense_acked names the dense fence
    # explicitly for the trainer's resend-queue pruner)
    assert r == {"ok": True, "acked": 0, "dense_acked": 0}
    r = ps._h_send_bucket({"g0": np.full(2, 1.0)}, trainer_id=0, aseq=1)
    # gap filled: fence jumps to 2
    assert r == {"ok": True, "acked": 2, "dense_acked": 2}
    assert applied == [("g0", 2.0), ("g0", 1.0)]
    # RPC-retry re-delivery straddling a restart: dropped, counted
    r = ps._h_send_bucket({"g0": np.full(2, 1.0)}, trainer_id=0, aseq=1)
    assert r.get("dup") and ps.counters["dedup_drops"] == 1
    assert applied == [("g0", 2.0), ("g0", 1.0)]
    # journal replay rebuilds the same applied stream + fence
    ps2 = _async_sparse_ps(str(tmp_path))
    applied2 = []
    ps2._apply_async_send_locked = \
        lambda name, value, _a=applied2: _a.append(
            (name, float(np.asarray(value).reshape(-1)[0])))
    assert ps2.load_checkpoint() is not None
    assert applied2 == applied
    assert ps2._dense_fence[0][0] == 2
    r = ps2._h_send_bucket({"g0": np.full(2, 2.0)}, trainer_id=0, aseq=2)
    assert r.get("dup"), "restored dense fence forgot an applied bucket"


def test_async_staleness_bound_parks_then_releases():
    """ACCEPTANCE (tentpole): a trainer running past
    FLAGS_async_staleness_bound is PARKED (its push blocks) and released
    the moment the slowest live peer advances — a fence on the clock
    gap, not a sleep."""
    ps = _async_sparse_ps(num_trainers=2, staleness_bound=2)
    # trainer 1 (the laggard) is at clock 1
    ps._h_send_sparse("t0", np.zeros(0, np.int64),
                      np.zeros((0, 4), np.float32), trainer_id=1, seq=1)
    # trainer 0 runs ahead: clocks 1..3 pass (gap <= 2)
    for s in range(1, 4):
        r = ps._h_send_sparse("t0", np.zeros(0, np.int64),
                              np.zeros((0, 4), np.float32),
                              trainer_id=0, seq=s)
        assert r["ok"]
    done = []
    th = threading.Thread(target=lambda: done.append(
        ps._h_send_sparse("t0", np.zeros(0, np.int64),
                          np.zeros((0, 4), np.float32),
                          trainer_id=0, seq=4)), daemon=True)
    th.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline \
            and ps.counters["staleness_parks"] < 1:
        time.sleep(0.01)
    assert ps.counters["staleness_parks"] == 1, "push was never parked"
    assert not done, "parked push returned before the laggard advanced"
    # the laggard advances one step: 4 - 2 == bound -> released
    ps._h_send_sparse("t0", np.zeros(0, np.int64),
                      np.zeros((0, 4), np.float32), trainer_id=1, seq=2)
    th.join(timeout=10)
    assert done and done[0]["ok"], "park never released"
    assert ps.counters["staleness_timeouts"] == 0
    assert ps.counters["parked_ms"] > 0


def test_async_staleness_released_by_departure():
    """Eviction / completion frees the bound (PR 1 liveness still
    guarantees progress): a parked fast trainer must not wait on a peer
    that is never coming back."""
    for depart in ("complete", "evict"):
        ps = _async_sparse_ps(num_trainers=2, staleness_bound=1)
        ps._h_send_sparse("t0", np.zeros(0, np.int64),
                          np.zeros((0, 4), np.float32), trainer_id=1,
                          seq=1)
        for s in range(1, 3):
            ps._h_send_sparse("t0", np.zeros(0, np.int64),
                              np.zeros((0, 4), np.float32),
                              trainer_id=0, seq=s)
        done = []
        th = threading.Thread(target=lambda: done.append(
            ps._h_send_sparse("t0", np.zeros(0, np.int64),
                              np.zeros((0, 4), np.float32),
                              trainer_id=0, seq=3)), daemon=True)
        th.start()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and ps.counters["staleness_parks"] < 1:
            time.sleep(0.01)
        assert ps.counters["staleness_parks"] == 1
        if depart == "complete":
            ps._h_complete(trainer_id=1)
        else:
            ps._h_evict(trainer_id=1)
        th.join(timeout=10)
        assert done and done[0]["ok"], \
            "%s did not release the parked trainer" % depart


def test_async_prefetch_parks_on_staleness():
    """The READ side of the bound: a lookup stamped with a clock past
    the bound parks too, so a fast trainer cannot even observe rows more
    than `bound` steps ahead of the laggard."""
    ps = _async_sparse_ps(num_trainers=2, staleness_bound=1)
    ps._h_send_sparse("t0", np.zeros(0, np.int64),
                      np.zeros((0, 4), np.float32), trainer_id=1, seq=1)
    got = []
    th = threading.Thread(target=lambda: got.append(
        ps._h_prefetch("t0", np.array([1, 2]), trainer_id=0, clock=5)),
        daemon=True)
    th.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline \
            and ps.counters["staleness_parks"] < 1:
        time.sleep(0.01)
    assert ps.counters["staleness_parks"] == 1 and not got
    ps._h_send_sparse("t0", np.zeros(0, np.int64),
                      np.zeros((0, 4), np.float32), trainer_id=1, seq=4)
    th.join(timeout=10)
    assert got and np.asarray(got[0]).shape == (2, 4)


def test_async_fenced_resend_after_incarnation_bump(tmp_path):
    """Client side of the fence, end to end over real RPC: the observed
    incarnation bump re-ships the un-acked chunk; the restored server's
    journal-fed fence dedupes what was already durable and applies what
    was not — and the client COUNTERS see all of it (the
    `_async_sends`-is-server-internal fix)."""
    from paddle_tpu.distributed import rpc as rpc_mod
    from paddle_tpu.ops import dist_ops

    rpc_mod.reset_comm_stats()
    dist_ops.reset_fences()
    ps = _async_sparse_ps(str(tmp_path))
    srv = VarServer("127.0.0.1:0", ps).start()
    ep = srv.endpoint
    try:
        cli = RPCClient(ep, timeout=10, retries=5, retry_wait=0.05)
        st = dist_ops._async_st(ep)
        cli.call("heartbeat", trainer_id=0)  # seeds the incarnation
        dist_ops._async_check_replay(cli, ep, 0)  # baselines ainc
        for i in range(2):
            ids, rows = _chunk(i)
            seq = st["sseq"].get("t0", 0) + 1
            st["sseq"]["t0"] = seq
            kw = dict(table="t0", ids=ids, rows=rows, trainer_id=0,
                      seq=seq)
            st["unacked"].setdefault("t0", {})[seq] = kw
            r = cli.call("send_sparse", **kw)
            dist_ops._async_note_ack(st, "t0", r)
            rpc_mod.note_async(async_sparse_sends=1)
        assert st["unacked"]["t0"] == {}, "acked chunks not pruned"
        # chunk 3 applies + journals server-side but the ACK is "lost"
        # (we keep it un-acked client-side), then the server dies
        ids, rows = _chunk(2)
        kw = dict(table="t0", ids=ids, rows=rows, trainer_id=0, seq=3)
        st["unacked"]["t0"][3] = kw
        cli.call("send_sparse", **kw)
        want = np.array(ps.sparse_tables["t0"]["tbl"])
        srv.shutdown()
        cli.close()  # a real SIGKILL severs the connection too: the
        # in-process shutdown leaves the old handler thread serving the
        # cached socket, which no killed process ever would
        ps2 = _async_sparse_ps(str(tmp_path))
        assert ps2.load_checkpoint() is not None
        ps2.incarnation = ps.incarnation + 1
        srv2 = VarServer(ep, ps2).start()
        try:
            cli.call("heartbeat", trainer_id=0)  # witnesses the bump
            dist_ops._async_check_replay(cli, ep, 0)
            # the re-shipped chunk was already durable: deduped, acked
            assert st["unacked"]["t0"] == {}
            np.testing.assert_array_equal(ps2.sparse_tables["t0"]["tbl"],
                                          want)
            stats = rpc_mod.get_comm_stats()
            assert stats["async_sparse_sends"] == 2
            assert stats["async_resends"] == 1
            assert stats["async_dedup_drops"] == 1
            assert stats["pserver_restarts_seen"] >= 1
            assert stats["recoveries"] >= 1
            # server-side observability: the stats verb exposes clocks,
            # journal and park evidence
            s = cli.call("stats", trainer_id=0)
            assert s["clocks"] == {"0": 3}
            assert s["journal_replayed"] == 3
            assert s["dedup_drops"] == 1
        finally:
            srv2.shutdown()
        cli.close()
    finally:
        srv.shutdown()
        rpc_mod.reset_comm_stats()
        dist_ops.reset_fences()
        with RPCClient._lock:
            RPCClient._instances.pop(ep, None)


def _table_dump(out, tag):
    """Parse one trainer's TABLE line out of [tag]-prefixed output."""
    for ln in out.splitlines():
        if ln.startswith("[%s] TABLE " % tag):
            return json.loads(ln[len("[%s] TABLE " % tag):])
    raise AssertionError("no TABLE line for %s in:\n%s" % (tag, out))


def _async_sparse_run(tmp_path, capfd, name, kill=False):
    """One supervised async sparse job (1 trainer, 1 pserver, journal
    armed); with kill=True the pserver is SIGKILLed mid-async-stream —
    AFTER a snapshot landed and journal records accumulated past it, so
    the restore exercises snapshot + journal-tail replay.  Returns
    (losses, table dump)."""
    from paddle_tpu.distributed.launch import _Cluster, _RestartPolicy

    port = _free_port()
    eps = "127.0.0.1:%d" % port
    ckpt = str(tmp_path / name)
    steps = 8
    full = dict(os.environ)
    full.update({
        "PADDLE_PSERVER_EPS": eps,
        "PADDLE_TRAINERS": "1",
        "DIST_SYNC_MODE": "0",
        "DIST_MODEL": "sparse",
        "DIST_DUMP_TABLE": "1",
        "DIST_STEPS": str(steps),
        "DIST_STEP_SLEEP": "0.2" if kill else "0",
        "PADDLE_PSERVER_CKPT_DIR": ckpt,
        # effectively suppress snapshots for this short job: the restore
        # is then a PURE journal replay (deterministic — a snapshot
        # landing between the kill fence and the kill would otherwise
        # race the journal rotation and cover the tail).  The
        # snapshot + journal-tail variant is proven deterministically by
        # the in-process tests above.
        "PADDLE_PSERVER_CKPT_EVERY": "50",
        "FLAGS_max_retry": "120",
        "JAX_PLATFORMS": "cpu",
    })
    cmd = [sys.executable, "-u", _RUNNER]
    ps_env = dict(full, PADDLE_TRAINING_ROLE="PSERVER",
                  PADDLE_CURRENT_ENDPOINT=eps)
    cluster = _Cluster()
    cluster.supervise("pserver.0", cmd, ps_env,
                      _RestartPolicy(max_restarts=3, backoff_s=0.2))
    cluster.spawn("pserver.0", cmd, ps_env)
    try:
        _wait_port(port)
        cluster.spawn("trainer.0", cmd,
                      dict(full, PADDLE_TRAINING_ROLE="TRAINER",
                           PADDLE_TRAINER_ID="0"))
        if kill:
            # FENCE, not a timer: applied updates are in the fsync'd
            # journal (and, with snapshots suppressed, NOWHERE else) —
            # the kill loses exactly the state only journal replay can
            # restore
            t0 = time.time()

            def journal_bytes():
                try:
                    return sum(
                        os.path.getsize(os.path.join(ckpt, fn))
                        for fn in os.listdir(ckpt)
                        if ".journal.seg" in fn)
                except OSError:
                    return 0

            while time.time() - t0 < 120 and journal_bytes() == 0:
                time.sleep(0.05)
            assert journal_bytes() > 0, "no journal before the kill"
            cluster.proc("pserver.0").kill()
        rc = cluster.wait()
    finally:
        cluster.kill()
    out = capfd.readouterr().out
    assert rc == 0, out
    if kill:
        assert cluster.restarts.get("pserver.0", 0) >= 1, out
        assert "JOURNAL-REPLAY" in out, out
    return _trainer_losses(out, "trainer.0"), _table_dump(out, "trainer.0")


@pytest.mark.slow  # two full cluster runs; rides scripts/ci.sh's async
#                    chaos pass (-m "") — the in-process journal/fence/
#                    staleness tests above are the tier-1 equivalent
def test_async_pserver_sigkill_loses_zero_applied_updates(tmp_path, capfd):
    """ACCEPTANCE (tentpole): async pserver SIGKILL + supervised restart
    loses ZERO applied sparse updates — the restored run's embedding
    table (and its whole loss trajectory) is BIT-IDENTICAL to an
    unkilled run of the same input stream.  Journal replay restores
    applied-but-unsnapshotted updates; the seq fence dedupes the
    client's at-least-once re-delivery of the in-flight chunk."""
    ref_losses, ref_table = _async_sparse_run(tmp_path, capfd, "ref",
                                              kill=False)
    kill_losses, kill_table = _async_sparse_run(tmp_path, capfd, "kill",
                                                kill=True)
    assert kill_losses == ref_losses, (
        "killed run's trajectory diverged: some applied update was lost "
        "or double-applied\nref=%s\nkill=%s" % (ref_losses, kill_losses))
    assert kill_table == ref_table, \
        "restored table is not bit-identical to the unkilled run's"


def test_pserver_kill_restart_resumes_from_manifest_checkpoint(tmp_path):
    """Acceptance: the pserver is SIGKILLed mid-training and restarted on
    the same port; it restores from the atomic checkpoint (manifest crc
    verified) and the trainer — retrying with backoff through the outage
    — finishes every step."""
    port = _free_port()
    eps = "127.0.0.1:%d" % port
    ckpt = str(tmp_path / "ckpt")
    common = {
        "PADDLE_PSERVER_EPS": eps,
        "PADDLE_TRAINERS": "1",
        "DIST_SYNC_MODE": "0",
        "DIST_STEPS": "8",
        "DIST_STEP_SLEEP": "0.2",
        "PADDLE_PSERVER_CKPT_DIR": ckpt,
        "PADDLE_PSERVER_CKPT_EVERY": "1",
        "FLAGS_max_retry": "120",
    }
    ps_env = dict(common, PADDLE_TRAINING_ROLE="PSERVER",
                  PADDLE_CURRENT_ENDPOINT=eps)
    ps1 = _spawn(ps_env)
    trainer = ps2 = None
    try:
        _wait_port(port)
        trainer = _spawn(dict(common, PADDLE_TRAINING_ROLE="TRAINER",
                              PADDLE_TRAINER_ID="0"))
        ckpt_file = os.path.join(ckpt, "pserver_0.ckpt")
        manifest = os.path.join(ckpt, "pserver_0.manifest.json")
        t0 = time.time()
        while time.time() - t0 < 90 and not (
                os.path.exists(ckpt_file) and os.path.exists(manifest)):
            time.sleep(0.1)
        assert os.path.exists(ckpt_file), "no checkpoint before the kill"
        assert os.path.exists(manifest), "no manifest before the kill"
        time.sleep(0.4)  # a couple more rounds land
        ps1.kill()
        ps1.wait()
        ps2 = _spawn(ps_env)
        losses, _ = _losses(trainer, timeout=240)
        assert len(losses) == 8
        assert np.isfinite(losses).all(), losses
        out, err = ps2.communicate(timeout=90)
        assert "PSERVER RESTORED" in out, (out, err)
    finally:
        for p in (ps1, ps2, trainer):
            if p is not None and p.poll() is None:
                p.kill()


# ---------------------------------------------------------------------------
# elastic autoscaling: plan epochs, stale-plan fence, scaling policy, chaos
# ---------------------------------------------------------------------------

def test_plan_epoch_fence_drops_stale_world_frames():
    """ACCEPTANCE (tentpole): a membership change mints a plan epoch at
    the round boundary; a frame still carrying the OLD epoch is fenced
    (dropped + told the current epoch) exactly like a stale
    incarnation — it can neither fold into a current-epoch round nor
    double-apply after the re-plan re-ships it."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=2,
                         sync_mode=True)
    applied = []
    ps._apply_shard = lambda idx, feed: applied.append(
        np.asarray(feed["g0"]).copy())
    # epoch 0: no fence — pepoch-less and pepoch=0 frames both flow
    assert ps._plan_epoch == 0
    with ps._cv:
        ps._evict_locked(1, "test")  # boundary: epoch mints immediately
    assert ps._plan_epoch == 1 and ps.counters["plan_epochs"] == 1
    # the survivor's next frame still carries epoch 0: FENCED
    r = ps._h_send_bucket({"g0": np.full(2, 3.0)}, trainer_id=0,
                          seq_total=1, step=1, seq_idx=0, pepoch=0)
    assert r.get("stale_plan") and r["pepoch"] == 1, r
    assert ps._round == 0 and not applied and not ps._pending, \
        "stale-world frame leaked into the round"
    assert ps.counters["stale_plan_drops"] == 1
    # sparse chunks are fenced the same way
    ps.sparse_tables["t0"] = {"tbl": np.zeros((4, 2), np.float32),
                              "lr": 0.1,
                              "opt": {"type": "sgd", "attrs": {}}}
    r = ps._h_send_sparse("t0", np.array([1]),
                          np.ones((1, 2), np.float32), trainer_id=0,
                          step=1, pepoch=0)
    assert r.get("stale_plan") and not ps._pending_sparse, r
    # the re-plan re-ships at the current epoch: applied exactly once
    r = ps._h_send_sparse("t0", np.array([1]),
                          np.ones((1, 2), np.float32), trainer_id=0,
                          step=1, pepoch=1)
    assert r == {"ok": True, "pepoch": 1}
    r = ps._h_send_bucket({"g0": np.full(2, 3.0)}, trainer_id=0,
                          seq_total=1, step=1, seq_idx=0, pepoch=1,
                          sparse_tables=["t0"])
    assert r == {"ok": True, "pepoch": 1} and ps._round == 1
    assert len(applied) == 1
    np.testing.assert_array_equal(applied[0], np.full(2, 3.0))
    # a FUTURE epoch (server restored from an older snapshot than the
    # sender's view — transiently possible) is never fenced
    r = ps._h_send_bucket({"g0": np.full(2, 1.0)}, trainer_id=0,
                          seq_total=1, step=2, seq_idx=0, pepoch=5)
    assert r.get("ok") and not r.get("stale_plan")


def test_plan_epoch_mint_deferred_to_round_boundary():
    """An eviction landing MID-ROUND must not bump the epoch under the
    survivors' in-flight frames (they would all be stale-fenced and the
    round could never complete): the mint waits for the boundary the
    round's completion creates."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=3,
                         sync_mode=True)
    ps._apply_shard = lambda idx, feed: None
    # trainer 0 contributes: the round is now being assembled
    r = ps._h_send_bucket({"g0": np.full(2, 1.0)}, trainer_id=0,
                          seq_total=2, step=1, seq_idx=0, pepoch=0)
    assert r == {"ok": True}
    with ps._cv:
        ps._evict_locked(2, "test")  # mid-round: mint must defer
    assert ps._plan_epoch == 0 and ps._plan_dirty, \
        "epoch minted mid-round — survivors' frames would stale-fence"
    # survivor 0 finishes its stream; survivor 1 folds; round runs;
    # the epoch mints AT the boundary
    done = []
    th = threading.Thread(target=lambda: done.append(
        ps._h_send_bucket({"g0": np.full(2, 1.0)}, trainer_id=0,
                          seq_total=2, step=1, seq_idx=1, pepoch=0)),
        daemon=True)
    th.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and 0 not in ps._send_barriers:
        time.sleep(0.01)
    r1 = ps._h_send_bucket({"g0": np.full(2, 5.0)}, trainer_id=1,
                           seq_total=1, step=1, seq_idx=0, pepoch=0)
    th.join(timeout=10)
    assert ps._round == 1
    assert ps._plan_epoch == 1 and not ps._plan_dirty
    # the post-round (blocking) replies told both survivors
    assert r1 == {"ok": True, "pepoch": 1}
    assert done and done[0] == {"ok": True, "pepoch": 1}


def test_plan_verb_reports_world_and_register_seeds_epoch():
    """The re-plan handshake: `plan` returns the current epoch + live
    world; a (re)joining trainer's register reply carries both so its
    first step plans for the world it actually joined."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=2,
                         sync_mode=True)
    ps._apply_shard = lambda idx, feed: None
    r = ps._h_plan(trainer_id=0)
    assert r == {"epoch": 0, "world": 2, "live": [0, 1], "trainers": 2,
                 "endpoints": []}
    with ps._cv:
        ps._evict_locked(1, "test")
    r = ps._h_plan(trainer_id=0)
    assert r["epoch"] == 1 and r["world"] == 1 and r["live"] == [0]
    # a NEW rank (elastic grow) registers: admitted, epoch re-mints,
    # and the reply carries the grown world
    r = ps._h_register(trainer_id=2)
    assert r["ok"] and r["world"] == 2 and r["pepoch"] == 2
    assert ps._live == {0, 2}
    assert ps.counters["plan_epochs"] == 2


def test_sparse_clocks_verb_advances_fences_and_clock():
    """The merged clock-only frame: one RPC advances every named
    table's fence monotonically and the trainer's logical clock to the
    newest seq — identical semantics to the n empty chunks it
    replaces."""
    ps = ParameterServer([], {}, num_trainers=2, sync_mode=False,
                         sparse_tables={
                             "t0": {"tbl": np.zeros((4, 2), np.float32)},
                             "t1": {"tbl": np.zeros((4, 2), np.float32)}})
    r = ps._h_sparse_clocks({"t0": 3, "t1": 5}, trainer_id=0)
    assert r == {"ok": True, "acked": 5}
    assert ps._sparse_fence == {(0, "t0"): 3, (0, "t1"): 5}
    assert ps._trainer_clock == {0: 5}
    # monotonic: a late/replayed lower clock cannot move fences back
    r = ps._h_sparse_clocks({"t0": 2, "t1": 4}, trainer_id=0)
    assert r == {"ok": True, "acked": 4}
    assert ps._sparse_fence == {(0, "t0"): 3, (0, "t1"): 5}
    assert ps._trainer_clock == {0: 5}
    # an evicted trainer's clocks are refused like its chunks
    with ps._cv:
        ps._evicted.add(1)
    assert ps._h_sparse_clocks({"t0": 9}, trainer_id=1) == {
        "ok": False, "evicted": True}


def test_terminal_evict_unparks_respawn_promise():
    """Restart-budget exhaustion: the supervisor's earlier respawn=True
    evict parked the id (job held open for the replacement); the
    terminal respawn=False evict retracts that promise — the id
    unparks, and an emptied world concludes the job NOW instead of at
    the eviction deadline."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=1,
                         sync_mode=True)
    ps._apply_shard = lambda idx, feed: None
    # supervised death: evict + park + immediate readmit (sole trainer)
    r = ps._h_evict(trainer_id=0, respawn=True)
    assert r["ok"] and ps._live == {0}, \
        "respawn-optimistic evict should readmit at the boundary"
    assert not ps._done.is_set()
    # budget exhausted: the promise is retracted — terminal
    r = ps._h_evict(trainer_id=0, respawn=False)
    assert r["ok"] and not ps._live and not ps._pending_joins
    assert ps._done.is_set(), \
        "terminal evict of the last id must conclude the job"


def test_scaling_policy_grow_shrink_and_damping():
    """_ScalingPolicy unit: hysteresis gates growth, stragglers shrink
    after persistent lag, cooldown and the _RestartPolicy action budget
    both damp flapping."""
    from paddle_tpu.distributed.launch import (
        _RestartPolicy,
        _ScalingPolicy,
    )

    pol = _ScalingPolicy(1, 3, cooldown_s=0.0, hysteresis=2,
                         budget=_RestartPolicy(max_restarts=2,
                                               window_s=60.0,
                                               backoff_s=0.0))
    pol._last_action = time.monotonic() - 10  # cooldown already served
    live = {"trainer.0", "trainer.1"}
    healthy = {"trainer.0": 3.0, "trainer.1": 3.0}
    assert pol.decide(live, healthy) is None  # hysteresis: streak 1
    assert pol.decide(live, healthy) == ("grow", None)
    # a trainer with UNKNOWN pace (just booted) blocks further growth
    live3 = live | {"trainer.2"}
    rates3 = dict(healthy, **{"trainer.2": None})
    assert pol.decide(live3, rates3) is None
    assert pol.decide(live3, rates3) is None
    # persistent straggler: flagged after `hysteresis` observations
    lagging = dict(healthy, **{"trainer.2": 0.5})
    assert pol.decide(live3, lagging) is None
    assert pol.decide(live3, lagging) == ("shrink", "trainer.2")
    # action budget (2 per window) exhausted: the next action is damped
    assert pol.decide(live3, lagging) is None
    assert pol.decide(live3, lagging) is None
    # cooldown damping: a fresh policy with a long cooldown sits still
    cold = _ScalingPolicy(1, 3, cooldown_s=3600.0, hysteresis=1)
    assert cold.decide(live, healthy) is None
    # shrink never drops below min (at the floor the policy may still
    # GROW toward max — it just cannot retire the straggler)
    floor = _ScalingPolicy(2, 3, cooldown_s=0.0, hysteresis=1)
    floor._last_action = time.monotonic() - 10
    d = floor.decide(live, {"trainer.0": 3.0, "trainer.1": 0.1})
    assert d is None or d[0] == "grow", d


def test_elastic_scale_down_sigkill_rescales_and_completes(capfd):
    """ACCEPTANCE (tentpole chaos E2E, scale-down): trainer 1 of 2 is
    SIGKILLed mid-job; the pservers evict it, mint a plan epoch at the
    next boundary (steps/s tracks the live count within ONE round of
    the change — the phase log pins it), the survivor re-derives its
    plan (grad scale 1/2 -> 1/1) and finishes every step with finite,
    convergent losses."""
    from paddle_tpu.distributed.launch import launch_pserver

    env = dict(os.environ)
    steps = 6
    env.update({
        "DIST_STEPS": str(steps),
        "DIST_STEP_SLEEP": "0.25",
        "DIST_CRASH_RANK": "1",
        "DIST_CRASH_AFTER_STEP": "1",
        "FLAGS_heartbeat_interval": "0.2",
        "FLAGS_eviction_deadline": "1.5",
        "FLAGS_max_retry": "120",
        "JAX_PLATFORMS": "cpu",
    })
    # the far-future chaos kill never fires: it marks trainer.1's
    # self-SIGKILL as the expected failure
    rc = launch_pserver([_RUNNER], nproc=2, n_pservers=2, base_env=env,
                        sync=True, chaos_kills=[("trainer.1", 9999.0)])
    out = capfd.readouterr().out
    assert rc == 0, out
    assert "PSERVER EVICT trainer=1" in out, out
    assert "PSERVER PLAN-EPOCH epoch=1 world=1" in out, out
    assert "TRAINER REPLAN epoch=1 world=1 corr=2" in out, out
    losses = _trainer_losses(out, "trainer.0")
    assert len(losses) == steps and np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses
    for ln in out.splitlines():
        if ln.startswith("[trainer.0] COUNTERS "):
            c = json.loads(ln[len("[trainer.0] COUNTERS "):])
            assert c["replans"] >= 1 and c["replan_ms"] > 0, c
            break
    else:
        raise AssertionError("no COUNTERS line:\n%s" % out)
    # phase log: membership phases moved 2 -> 1 within one round of the
    # kill (the epoch-1 phase starts at most one round after the
    # epoch-0 phase's last assembled round)
    for ln in out.splitlines():
        if ln.startswith("[pserver.0] PSERVER-STATS "):
            s = json.loads(ln[len("[pserver.0] PSERVER-STATS "):])
            worlds = [p["world"] for p in s["phases"]]
            assert worlds == [2, 1], s["phases"]
            assert s["plan_epoch"] == 1 and s["plan_epochs"] == 1, s
            # steps/s tracked the membership: the shrunk phase ran the
            # remaining rounds (steps - the 2-trainer phase's rounds)
            assert s["phases"][1]["rounds"] == steps - \
                s["phases"][0]["rounds"], s["phases"]
            break
    else:
        raise AssertionError("no PSERVER-STATS line:\n%s" % out)


@pytest.mark.slow  # two JAX boots + a policy window; rides scripts/ci.sh
def test_elastic_policy_grow_adds_trainer_and_rescales(capfd):
    """ACCEPTANCE (tentpole chaos E2E, policy-driven scale-up): a 1:2
    elastic job starts with ONE trainer; the supervisor's policy loop
    observes steady step progress, grows trainer.1, the pserver admits
    it at a round boundary and mints a plan epoch, and BOTH trainers
    re-derive (corr 1 -> 0.5) and finish with finite losses."""
    from paddle_tpu.distributed.launch import launch_pserver

    env = dict(os.environ)
    # long enough that the grown trainer (policy window + a jax boot)
    # joins before the job ends: 14 steps finished first under jax 0.9.0
    steps = 40
    env.update({
        "DIST_STEPS": str(steps),
        "DIST_STEP_SLEEP": "0.3",
        "FLAGS_max_retry": "120",
        "JAX_PLATFORMS": "cpu",
    })
    rc = launch_pserver([_RUNNER], nproc=1, n_pservers=1, base_env=env,
                        sync=True, supervise=True, restart_backoff=0.2,
                        elastic="1:2", elastic_cooldown=1.0)
    cap = capfd.readouterr()
    out = cap.out
    assert rc == 0, out
    assert "ELASTIC GROW trainer.1" in cap.err, cap.err
    assert "TRAINER REPLAN epoch=1 world=2" in out, out
    assert "PSERVER PLAN-EPOCH epoch=1 world=2" in out, out
    assert "TRAINER REPLAN epoch=1 world=2 corr=0.5" in out, out
    l0 = _trainer_losses(out, "trainer.0")
    assert len(l0) == steps and np.isfinite(l0).all(), l0
    # the grown trainer either finished its run or was retired cleanly
    # at winddown; if it finished, its losses are finite too
    for ln in out.splitlines():
        if ln.startswith("[trainer.1] LOSSES "):
            l1 = json.loads(ln[len("[trainer.1] LOSSES "):])
            assert np.isfinite(l1).all(), l1
            break


@pytest.mark.slow  # three JAX boots; rides scripts/ci.sh elastic pass
def test_elastic_kill_during_replan_cannot_hang_round(capfd):
    """ACCEPTANCE (tentpole chaos E2E, the re-plan race): trainer 2
    dies at step 1 (epoch mints, survivors re-plan); trainer 1 dies at
    step 3 — right in the window where the epoch-1 re-plan is
    propagating.  The sole survivor must keep completing rounds (no
    hang) and finish every step with finite losses; the plan-epoch
    fence guarantees no bucket double-applied across the two
    re-plans."""
    from paddle_tpu.distributed.launch import _Cluster

    port = _free_port()
    eps = "127.0.0.1:%d" % port
    steps = 8
    common = dict(os.environ)
    common.update({
        "PADDLE_PSERVER_EPS": eps,
        "PADDLE_TRAINERS": "3",
        "DIST_SYNC_MODE": "1",
        "DIST_STEPS": str(steps),
        "DIST_STEP_SLEEP": "0.25",
        "FLAGS_heartbeat_interval": "0.2",
        "FLAGS_eviction_deadline": "1.5",
        "FLAGS_max_retry": "120",
        "JAX_PLATFORMS": "cpu",
    })
    cmd = [sys.executable, "-u", _RUNNER]
    cluster = _Cluster()

    def notify(tag, rc):
        if not tag.startswith("trainer."):
            return
        tid = int(tag.split(".", 1)[1])
        cli = RPCClient(eps, timeout=2, retries=2, retry_wait=0.1)
        try:
            cli.call("evict", trainer_id=tid, deadline_s=5.0,
                     respawn=False)
        except Exception:
            pass
        finally:
            cli.close()

    cluster.on_child_death = notify
    cluster.spawn("pserver.0", cmd,
                  dict(common, PADDLE_TRAINING_ROLE="PSERVER",
                       PADDLE_CURRENT_ENDPOINT=eps))
    try:
        _wait_port(port)
        cluster.spawn("trainer.0", cmd,
                      dict(common, PADDLE_TRAINING_ROLE="TRAINER",
                           PADDLE_TRAINER_ID="0"))
        for rank, crash_after in ((1, 3), (2, 1)):
            cluster.expect_failure("trainer.%d" % rank)
            cluster.spawn(
                "trainer.%d" % rank, cmd,
                dict(common, PADDLE_TRAINING_ROLE="TRAINER",
                     PADDLE_TRAINER_ID=str(rank),
                     DIST_CRASH_RANK=str(rank),
                     DIST_CRASH_AFTER_STEP=str(crash_after)))
        rc = cluster.wait()
    finally:
        cluster.kill()
    out = capfd.readouterr().out
    assert rc == 0, out
    assert "PSERVER EVICT trainer=2" in out, out
    assert "PSERVER EVICT trainer=1" in out, out
    # two durable shrinks -> two plan epochs, worlds 3 -> 2 -> 1
    assert "PSERVER PLAN-EPOCH epoch=1 world=2" in out, out
    assert "PSERVER PLAN-EPOCH epoch=2 world=1" in out, out
    assert "TRAINER REPLAN epoch=2 world=1 corr=3" in out, out
    losses = _trainer_losses(out, "trainer.0")
    assert len(losses) == steps and np.isfinite(losses).all(), losses


@pytest.mark.slow  # two supervised respawn cycles; rides scripts/ci.sh
def test_restart_budget_exhaustion_fails_clean_with_terminal_evict(capfd):
    """Satellite chaos: a trainer that crashes EVERY incarnation
    exhausts --max-restarts; the cluster fails the job cleanly —
    nonzero exit well before any eviction deadline could be waited out,
    the budget-exhaustion notice printed, and the survivors' pservers
    told the id is terminal (respawn=False evict — the in-process
    semantics are pinned by test_terminal_evict_unparks_respawn_
    promise)."""
    from paddle_tpu.distributed.launch import launch_pserver

    env = dict(os.environ)
    env.update({
        "DIST_STEPS": "30",
        "DIST_STEP_SLEEP": "0.25",
        "DIST_CRASH_RANK": "1",
        "DIST_CRASH_AFTER_STEP": "0",  # crashes at step 0, EVERY life
        # a deadline far beyond the test budget: only the terminal
        # evict path can conclude the cluster this fast
        "FLAGS_eviction_deadline": "120",
        "FLAGS_heartbeat_interval": "2.0",
        "FLAGS_max_retry": "120",
        "JAX_PLATFORMS": "cpu",
    })
    t0 = time.monotonic()
    rc = launch_pserver([_RUNNER], nproc=2, n_pservers=1, base_env=env,
                        sync=True, supervise=True, max_restarts=1,
                        restart_window=60.0, restart_backoff=0.2)
    wall = time.monotonic() - t0
    out = capfd.readouterr()
    assert rc != 0, out.out
    assert "restart budget exhausted" in out.err, out.err
    assert wall < 110, (
        "cluster waited out the eviction deadline instead of failing "
        "on the terminal evict (%.0fs)" % wall)


def test_restored_server_remembers_admitted_elastic_rank(tmp_path):
    """Found by the combined elastic+pserver-kill drive: a restored
    server used to rebuild its live set from range(num_trainers) minus
    departed — an elastic-grown rank (>= the transpile-time count) was
    forgotten, so the job was declared done under it the moment the
    original ranks completed.  The live set now rides the snapshot."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=2,
                         sync_mode=True, checkpoint_dir=str(tmp_path),
                         server_idx=0, checkpoint_every=1)
    ps._apply_shard = lambda idx, feed: None
    assert ps._h_register(trainer_id=2)["ok"]  # elastic grow: rank 2
    assert ps._live == {0, 1, 2}
    # a round lands a snapshot containing the grown world
    for tid in (0, 1, 2):
        threading.Thread(
            target=ps._h_send_bucket,
            kwargs=dict(blocks={"g0": np.ones(2)}, trainer_id=tid,
                        seq_total=1, step=1, seq_idx=0),
            daemon=True).start()
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline and ps._round < 1:
        time.sleep(0.02)
    assert ps._round == 1
    mpath = tmp_path / "pserver_0.manifest.json"
    while time.monotonic() < deadline and not (
            mpath.exists()
            and json.loads(mpath.read_text())["round"] == 1):
        time.sleep(0.05)
    ps2 = ParameterServer([None], {"g0": 0}, num_trainers=2,
                          sync_mode=True, checkpoint_dir=str(tmp_path),
                          server_idx=0)
    ps2._apply_shard = lambda idx, feed: None
    assert ps2.load_checkpoint() == 1
    assert ps2._live == {0, 1, 2}, \
        "restored server forgot the admitted elastic rank"
    # the original ranks completing must NOT conclude the job under the
    # grown rank
    ps2._h_complete(trainer_id=0)
    ps2._h_complete(trainer_id=1)
    assert not ps2._done.is_set() and ps2._live == {2}
    ps2._h_complete(trainer_id=2)
    assert ps2._done.is_set()


def test_clock_flush_runs_incarnation_replay_before_fence_advance(
        tmp_path):
    """Review finding, pinned: the merged sparse_clocks frame must run
    the incarnation-replay check BEFORE shipping — the frame advances
    the per-table seq fence, and letting it move past an un-acked data
    chunk on a restarted server would make the eventual re-send drop
    as `dup`: a silently lost update."""
    from paddle_tpu.distributed import rpc as rpc_mod
    from paddle_tpu.ops import dist_ops

    rpc_mod.reset_comm_stats()
    dist_ops.reset_fences()
    ps = _async_sparse_ps(str(tmp_path))
    srv = VarServer("127.0.0.1:0", ps).start()
    ep = srv.endpoint
    try:
        cli = RPCClient(ep, timeout=10, retries=5, retry_wait=0.05)
        st = dist_ops._async_st(ep)
        cli.call("heartbeat", trainer_id=0)
        dist_ops._async_check_replay(cli, ep, 0)  # baselines ainc
        # seq 1 applied + acked normally
        ids, rows = _chunk(0)
        st["sseq"]["t0"] = 1
        kw = dict(table="t0", ids=ids, rows=rows, trainer_id=0, seq=1)
        st["unacked"].setdefault("t0", {})[1] = kw
        dist_ops._async_note_ack(st, "t0", cli.call("send_sparse", **kw))
        # seq 2 is minted and queued but NEVER reaches the server (the
        # crash ate both the apply and the ack)
        ids2, rows2 = _chunk(1)
        st["sseq"]["t0"] = 2
        st["unacked"]["t0"][2] = dict(table="t0", ids=ids2, rows=rows2,
                                      trainer_id=0, seq=2)
        srv.shutdown()
        cli.close()
        ps2 = _async_sparse_ps(str(tmp_path))
        assert ps2.load_checkpoint() is not None
        ps2.incarnation = ps.incarnation + 1
        srv2 = VarServer(ep, ps2).start()
        try:
            cli.call("heartbeat", trainer_id=0)  # witnesses the bump
            # next step is rowless for t0: the clock-only path buffers
            # seq 3 and flushes ONE merged frame — which must re-ship
            # the lost seq-2 chunk FIRST
            st["sseq"]["t0"] = 3
            clk = {"n": 1, "seen": 0, "pending": {ep: {"t0": 3}}}
            dist_ops._clk_flush(clk, lambda e, t: RPCClient.get(e), 0)
            assert st["unacked"]["t0"] == {}, \
                "un-acked chunk not re-shipped before the clock frame"
            assert ps2._sparse_fence[(0, "t0")] == 3
            # the seq-2 update LANDED (not dropped as dup past a fence)
            want = np.array(ps.sparse_tables["t0"]["tbl"])
            ids2u = np.asarray(ids2).reshape(-1)
            assert not np.allclose(
                ps2.sparse_tables["t0"]["tbl"][ids2u], want[ids2u]), \
                "re-shipped chunk was dropped — update silently lost"
            stats = rpc_mod.get_comm_stats()
            assert stats["async_resends"] == 1
            assert stats["async_clock_merges"] == 1
        finally:
            srv2.shutdown()
        cli.close()
    finally:
        srv.shutdown()
        rpc_mod.reset_comm_stats()
        dist_ops.reset_fences()
        with RPCClient._lock:
            RPCClient._instances.pop(ep, None)


# ---------------------------------------------------------------------------
# live pserver shard migration: journaled handoff, two-phase commit,
# load-aware scaling, elastic collective (docs/FAULT_TOLERANCE.md
# "Live shard migration")
# ---------------------------------------------------------------------------
def _mig_spec(eps, trainers=1, wire="float32", grad_int8=False):
    return {"params": [], "endpoints": [str(e) for e in eps],
            "trainers": int(trainers),
            "flags": {"slice_var_up": True, "min_block_size": 4,
                      "split_method": "SizeWeighted",
                      "comm_bucket_bytes": 4096,
                      "comm_wire_dtype": wire,
                      "comm_grad_int8": bool(grad_int8)}}


def _mig_ps(base_eps, endpoint, shards=None, ckpt=None, server_idx=0,
            with_slots=False, **kw):
    """Migration-capable in-process pserver: real plan spec + sparse
    shards keyed by their stable BASE index."""
    tables, idx = {}, {}
    for name, s in (shards or {}).items():
        tbl = (np.arange(24, dtype=np.float32).reshape(6, 4)
               + 10.0 * (s + 1))
        info = {"tbl": tbl, "lr": 0.1, "opt": {"type": "sgd",
                                               "attrs": {}}}
        if with_slots:
            info["opt"] = {"type": "adagrad", "attrs": {"epsilon": 1e-6}}
            info["moment"] = np.full_like(tbl, 0.5)
        tables[name] = info
        idx[name] = s
    ps = ParameterServer(
        [], {}, num_trainers=1, sync_mode=True, checkpoint_dir=ckpt,
        server_idx=server_idx, sparse_tables=tables,
        plan_spec=_mig_spec(base_eps), endpoint=str(endpoint),
        ps_world=[str(e) for e in base_eps], sparse_shard_idx=idx, **kw)
    ps._apply_shard = lambda i, f: None
    ps.eviction_deadline = 1.0  # short freeze/boundary limits in tests
    return ps


def test_migration_handoff_in_process_bit_exact():
    """ACCEPTANCE (in-process handoff): a sparse shard's table, slot
    state and seq fences move whole through the crc-framed journal
    transport and land BIT-IDENTICAL at the target; the plan epoch
    mints only at commit, and the source drops its copy only then."""
    base = ["10.9.9.9:1"]
    src = _mig_ps(base, base[0], shards={"t0.shard0": 0},
                  with_slots=True)
    src._sparse_fence[(0, "t0.shard0")] = 7
    want_tbl = np.array(src.sparse_tables["t0.shard0"]["tbl"])
    want_m = np.array(src.sparse_tables["t0.shard0"]["moment"])
    tgt = _mig_ps(base, None)  # endpoint assigned below (listen first)
    srv = VarServer("127.0.0.1:0", tgt).start()
    tgt.endpoint = srv.endpoint
    new_world = [srv.endpoint]
    try:
        r = src._h_migrate_begin(world=new_world)
        assert r["ok"] and r["moved"] == 1 and r["bytes"] > 0, r
        # begin shipped + target fsynced — but NOTHING minted yet, and
        # the source still owns (and serves) the shard
        assert src._plan_epoch == 0 and tgt._plan_epoch == 0
        assert "t0.shard0" in src.sparse_tables
        np.testing.assert_array_equal(
            tgt.sparse_tables["t0.shard0"]["tbl"], want_tbl)
        np.testing.assert_array_equal(
            tgt.sparse_tables["t0.shard0"]["moment"], want_m)
        assert tgt._sparse_fence[(0, "t0.shard0")] == 7
        assert tgt._sparse_shard_idx["t0.shard0"] == 0
        r = src._h_migrate_commit(world=new_world)
        assert r["ok"] and r["retiring"], r
        assert src._plan_epoch == 1
        assert "t0.shard0" not in src.sparse_tables
        assert src._ps_world == new_world
        # the target learns the world via ITS commit (recovery path —
        # it never began; nothing moves off it)
        r = tgt._h_migrate_commit(world=new_world)
        assert r["ok"] and not r["retiring"], r
        assert tgt._ps_world == new_world and tgt._plan_epoch == 1
        np.testing.assert_array_equal(
            tgt.sparse_tables["t0.shard0"]["tbl"], want_tbl)
    finally:
        srv.shutdown()
        with RPCClient._lock:
            RPCClient._instances.pop(srv.endpoint, None)


def test_epoch_never_mints_before_target_durability():
    """ACCEPTANCE: the target dies between replay and ack (its
    migrate_in raises after applying) — the begin ABORTS, the epoch
    never mints, the old assignment stays authoritative, and the source
    keeps APPLYING updates with zero drops (trainers keep dispatching
    to it)."""
    base = ["10.9.9.8:1"]
    src = _mig_ps(base, base[0], shards={"t0.shard0": 0})
    tgt = _mig_ps(base, None)
    real = tgt._h_migrate_in

    def die_before_ack(frames, source=None, trainer_id=0):
        real(frames, source=source, trainer_id=trainer_id)
        raise RuntimeError("SIGKILL between replay and ack")

    tgt._h_migrate_in = die_before_ack
    srv = VarServer("127.0.0.1:0", tgt).start()
    tgt.endpoint = srv.endpoint
    try:
        before = np.array(src.sparse_tables["t0.shard0"]["tbl"])
        r = src._h_migrate_begin(world=[srv.endpoint])
        assert not r["ok"], r
        # nothing minted, nothing dropped, not frozen
        assert src._plan_epoch == 0 and src._mig is None
        assert not src._frozen
        assert src._ps_world == base
        assert "t0.shard0" in src.sparse_tables
        # trainers keep dispatching to the source: the update APPLIES
        r = src._h_send_sparse(table="t0.shard0",
                               ids=np.array([1], np.int64),
                               rows=np.ones((1, 4), np.float32),
                               trainer_id=0)
        assert r["ok"] and not r.get("stale_plan"), r
        with src._cv:
            src._run_round()  # sync mode queues; the round applies it
        after = np.array(src.sparse_tables["t0.shard0"]["tbl"])
        assert not np.array_equal(before, after), \
            "the applied update was dropped"
        assert src.counters["migrate_aborts"] == 1
    finally:
        srv.shutdown()
        with RPCClient._lock:
            RPCClient._instances.pop(srv.endpoint, None)


def test_migrate_commit_recovery_after_source_restart(tmp_path):
    """A source killed between its begin-ack and its commit restores
    WITHOUT the in-memory capture; the driver's commit retry hits the
    RECOVERY path: the diff is recomputed, the (already-durable-at-
    target) shards drop, the world adopts, the epoch mints — no
    re-begin after a mint, so no stale copy can overwrite the target."""
    base = ["10.9.9.7:1"]
    src = _mig_ps(base, base[0], shards={"t0.shard0": 0},
                  ckpt=str(tmp_path), server_idx=11)
    src.save_checkpoint()
    tgt = _mig_ps(base, None)
    srv = VarServer("127.0.0.1:0", tgt).start()
    tgt.endpoint = srv.endpoint
    new_world = [srv.endpoint]
    try:
        assert src._h_migrate_begin(world=new_world)["ok"]
        # "SIGKILL" the source: a fresh incarnation restores from the
        # pre-handoff snapshot (no _mig capture survives)
        src2 = _mig_ps(base, base[0], shards={"t0.shard0": 0},
                       ckpt=str(tmp_path), server_idx=11)
        assert src2.load_checkpoint() is not None
        assert src2._mig is None
        r = src2._h_migrate_commit(world=new_world)
        assert r["ok"] and r["retiring"], r
        assert src2._plan_epoch == 1
        assert "t0.shard0" not in src2.sparse_tables
        assert src2._ps_world == new_world
        # idempotent: a second commit (driver retry) acks cleanly
        r = src2._h_migrate_commit(world=new_world)
        assert r["ok"], r
    finally:
        srv.shutdown()
        with RPCClient._lock:
            RPCClient._instances.pop(srv.endpoint, None)


def test_migrated_state_survives_target_restart(tmp_path):
    """Adopted shards are DURABLE before the ack: a target SIGKILLed
    right after migrate_in restores them (snapshot + adopted-state
    registry), bit-identical — the epoch-mint-after-durability
    invariant is meaningful only because of this."""
    base = ["10.9.9.6:1"]
    src = _mig_ps(base, base[0], shards={"t0.shard0": 0},
                  with_slots=True)
    want = np.array(src.sparse_tables["t0.shard0"]["tbl"])
    tgt = _mig_ps(base, None, ckpt=str(tmp_path), server_idx=21)
    srv = VarServer("127.0.0.1:0", tgt).start()
    tgt.endpoint = srv.endpoint
    try:
        assert src._h_migrate_begin(world=[srv.endpoint])["ok"]
    finally:
        srv.shutdown()
        with RPCClient._lock:
            RPCClient._instances.pop(srv.endpoint, None)
    tgt2 = _mig_ps(base, tgt.endpoint, ckpt=str(tmp_path),
                   server_idx=21)
    assert tgt2.load_checkpoint() is not None
    np.testing.assert_array_equal(
        tgt2.sparse_tables["t0.shard0"]["tbl"], want)
    np.testing.assert_array_equal(
        tgt2.sparse_tables["t0.shard0"]["moment"],
        src.sparse_tables["t0.shard0"]["moment"])
    assert tgt2._sparse_shard_idx["t0.shard0"] == 0


def test_delta_migration_dirty_tail_and_freeze_shrink():
    """ACCEPTANCE (incremental delta handoff, ROADMAP 3a): a LARGE
    embedding shard ships as an UNFROZEN snapshot while the source
    keeps applying updates; only the rows dirtied in between ride the
    frozen final tail (an `mrows` record, a tiny fraction of the
    snapshot bytes), land bit-exact at the target — and the frozen
    window shrinks versus the full-copy handoff of the same shard,
    where the freeze spans the whole serialize+ship."""
    n, dim = 20000, 32

    def big_src(base):
        s = _mig_ps(base, base[0], shards={"emb.shard0": 0},
                    with_slots=True)
        info = s.sparse_tables["emb.shard0"]
        rng = np.random.RandomState(3)
        info["tbl"] = rng.rand(n, dim).astype(np.float32)
        info["moment"] = np.full((n, dim), 0.5, np.float32)
        return s

    def run_leg(base, delta, mutate_between=False):
        src = big_src(base)
        tgt = _mig_ps(base, None)
        srv = VarServer("127.0.0.1:0", tgt).start()
        tgt.endpoint = srv.endpoint
        ship = {"frames": []}
        real = tgt._h_migrate_in

        def spy(frames, source=None, trainer_id=0):
            r = real(frames, source=source, trainer_id=trainer_id)
            ship["frames"].append([bytes(f) for f in frames])
            if mutate_between and len(ship["frames"]) == 1:
                # between the unfrozen snapshot and the freeze: the
                # source is still serving — this application must ride
                # the dirty-row tail, not be lost
                with src._cv:
                    src._apply_sparse(
                        "emb.shard0", np.array([1, 5, 9], np.int64),
                        np.ones((3, dim), np.float32))
            return r

        tgt._h_migrate_in = spy
        try:
            r = src._h_migrate_begin(world=[srv.endpoint], delta=delta)
            assert r["ok"], r
            assert src._h_migrate_commit(world=[srv.endpoint])["ok"]
        finally:
            srv.shutdown()
            with RPCClient._lock:
                RPCClient._instances.pop(srv.endpoint, None)
        return src, tgt, r, ship["frames"]

    # full-copy reference: ONE migrate_in, inside the freeze
    _, tgt_f, r_full, ships_f = run_leg(["10.9.9.5:1"], delta=False)
    assert len(ships_f) == 1
    # delta: snapshot ships first (unfrozen), the tail second (frozen)
    src_d, tgt_d, r_delta, ships_d = run_leg(
        ["10.9.9.4:1"], delta=True, mutate_between=True)
    assert len(ships_d) == 2, "expected snapshot + frozen tail"
    kinds = [ParameterServer._mig_unframe(f)["k"] for f in ships_d[1]]
    assert "mrows" in kinds, kinds
    # the mid-handoff update landed bit-exact (rows 1/5/9 overlaid):
    # the target must equal a reference server that saw the SAME apply
    assert src_d.sparse_tables.get("emb.shard0") is None  # committed away
    ref_src = big_src(["10.9.9.3:1"])
    with ref_src._cv:
        ref_src._apply_sparse("emb.shard0",
                              np.array([1, 5, 9], np.int64),
                              np.ones((3, dim), np.float32))
    for field in ("tbl", "moment"):
        np.testing.assert_array_equal(
            tgt_d.sparse_tables["emb.shard0"][field],
            ref_src.sparse_tables["emb.shard0"][field])
    np.testing.assert_array_equal(
        tgt_f.sparse_tables["emb.shard0"]["tbl"],
        big_src(["10.9.9.2:1"]).sparse_tables["emb.shard0"]["tbl"])
    # the frozen tail is a tiny fraction of the snapshot bytes...
    tail = sum(len(f) for f in ships_d[1])
    snap = sum(len(f) for f in ships_d[0])
    assert tail < 0.05 * snap, (tail, snap)
    # ...and the frozen WINDOW shrinks vs the full-copy handoff
    assert r_delta["freeze_ms"] < r_full["freeze_ms"], (r_delta, r_full)


class _StubPipe:
    """Capture-everything stand-in for the PipelinedClient map."""

    def __init__(self):
        self.shipped = {}  # ep -> [kwargs]

    def __call__(self, ep):
        pipe = self

        class P:
            def submit(self, verb, timeout_s=None, **kw):
                pipe.shipped.setdefault(ep, []).append((verb, kw))

            def drain(self):
                return []

        return P()


@pytest.mark.parametrize("wire", ["bf16", "int8"])
def test_transition_round_rescales_exactly(wire):
    """ACCEPTANCE (PR 10 gap closed): the stale-plan replay's transition
    round is EXACT under a compressed wire — the re-shipped block is
    compress(raw * ratio), re-compressed from the recorded
    pre-compression value, never rescaled-compressed bytes; the int8
    error-feedback residual is re-derived from the replacing
    quantization."""
    from paddle_tpu.distributed.rpc import Bf16Wire, Int8Wire
    from paddle_tpu.ops import dist_ops

    dist_ops.reset_fences()
    ep = "10.9.9.5:1"
    wire_dtype = "bfloat16" if wire == "bf16" else "float32"
    grad_int8 = wire == "int8"
    rng = np.random.RandomState(3)
    raw = rng.randn(32).astype(np.float32)
    raw_out = {}
    shipped0 = dist_ops._compress_block(ep, "g.block0", raw, wire_dtype,
                                        grad_int8, raw_out=raw_out)
    assert "g.block0" in raw_out
    fst = dist_ops._fence(ep)
    fst.update(step=1, corr=1.0, raw=dict(raw_out))
    fst["sends"] = [dict(blocks={"g.block0": shipped0}, trainer_id=0,
                         seq_total=1, step=1, seq_idx=0,
                         sparse_tables=[])]
    st = {"spec": _mig_spec([ep], trainers=2, wire=wire_dtype,
                            grad_int8=grad_int8),
          "epoch": 1, "base": 2, "world": 1, "corr": 2.0,
          "derived": None, "replans": 0}
    pipe = _StubPipe()
    try:
        dist_ops._replay_round_plan(pipe, 0, [ep], st, set())
        kws = [kw for verb, kw in pipe.shipped[ep]
               if verb == "send_bucket"]
        assert len(kws) == 1
        got = kws[0]["blocks"]["g.block0"]
        assert kws[0]["pepoch"] == 1
        want_raw = (raw * np.float32(2.0)).astype(np.float32)
        if wire == "bf16":
            assert isinstance(got, Bf16Wire)
            import ml_dtypes

            np.testing.assert_array_equal(
                got.arr.astype(ml_dtypes.bfloat16),
                want_raw.astype(ml_dtypes.bfloat16))
        else:
            assert isinstance(got, Int8Wire)
            q2, scale2, deq2 = dist_ops._quantize_i8(want_raw)
            np.testing.assert_array_equal(got.q, q2)
            assert got.scale == scale2
            # the residual now corresponds to the REPLACING quantization
            np.testing.assert_allclose(
                dist_ops._ef_residuals[(ep, "g.block0")],
                want_raw - deq2, rtol=0, atol=0)
            # and is NOT the stale original-scale residual
            _q1, _s1, deq1 = dist_ops._quantize_i8(raw)
            assert not np.allclose(want_raw - deq2, raw - deq1)
    finally:
        dist_ops.reset_fences()


def test_transition_round_rescale_is_idempotent_at_ratio_one():
    """A pserver-set-only change (trainer count unchanged, ratio 1)
    re-ships BYTE-identical compressed blocks — re-compression of the
    unchanged raw reproduces the original quantization and residual."""
    from paddle_tpu.distributed.rpc import Int8Wire
    from paddle_tpu.ops import dist_ops

    dist_ops.reset_fences()
    ep = "10.9.9.4:1"
    raw = np.linspace(-1, 1, 16).astype(np.float32)
    raw_out = {}
    shipped0 = dist_ops._compress_block(ep, "g.block0", raw, "float32",
                                        True, raw_out=raw_out)
    res0 = np.array(dist_ops._ef_residuals[(ep, "g.block0")])
    got = dist_ops._recompress_block(ep, "g.block0",
                                     raw_out["g.block0"], "float32",
                                     True)
    assert isinstance(got, Int8Wire)
    np.testing.assert_array_equal(got.q, shipped0.q)
    assert got.scale == shipped0.scale
    np.testing.assert_array_equal(
        dist_ops._ef_residuals[(ep, "g.block0")], res0)
    dist_ops.reset_fences()


def test_fault_delay_is_seeded_and_bounded():
    """Satellite: the `delay` action's per-frame latency is a pure
    function of (seed, frame index) — deterministic across schedules
    with the same seed, different across seeds, always in (0, 1]."""
    a = FaultSchedule(seed=5)
    b = FaultSchedule(seed=5)
    c = FaultSchedule(seed=6)
    fr_a = [a.delay_fraction(i) for i in range(64)]
    assert fr_a == [b.delay_fraction(i) for i in range(64)]
    assert fr_a != [c.delay_fraction(i) for i in range(64)]
    assert all(0.0 < f <= 1.0 for f in fr_a)
    assert len(set(fr_a)) > 32  # actually varies per frame


def test_delayed_handoff_still_completes_within_epoch_fence():
    """Satellite: a SLOW network (every handoff frame delayed, none
    lost) delivers the migration late but intact — the handoff
    completes, the table lands bit-identical, and the epoch still only
    mints at commit (the fence is ordering, not timing)."""
    base = ["10.9.9.3:1"]
    src = _mig_ps(base, base[0], shards={"t0.shard0": 0})
    want = np.array(src.sparse_tables["t0.shard0"]["tbl"])
    tgt = _mig_ps(base, None)
    srv = VarServer("127.0.0.1:0", tgt).start()
    chan = FaultyChannel(srv.endpoint, delay=1.0, seed=5,
                         delay_s=0.2).start()
    tgt.endpoint = chan.endpoint
    new_world = [chan.endpoint]
    try:
        t0 = time.monotonic()
        r = src._h_migrate_begin(world=new_world)
        assert r["ok"], r
        assert src._plan_epoch == 0  # delayed, delivered, not yet minted
        np.testing.assert_array_equal(
            tgt.sparse_tables["t0.shard0"]["tbl"], want)
        assert src._h_migrate_commit(world=new_world)["ok"]
        assert src._plan_epoch == 1
        assert chan.stats["c2s"]["delay"] >= 1, chan.stats
        assert time.monotonic() - t0 < 30.0
    finally:
        chan.stop()
        srv.shutdown()
        with RPCClient._lock:
            RPCClient._instances.pop(chan.endpoint, None)


def test_scaling_policy_pserver_load_signals():
    """Load-aware pserver scaling: persistent queue-depth pressure grows
    (after hysteresis), sustained idleness shrinks (double hysteresis),
    stale-plan drops SUPPRESS actions (a membership change is still
    settling), and the shared action budget damps flapping."""
    from paddle_tpu.distributed.launch import _RestartPolicy, \
        _ScalingPolicy

    pol = _ScalingPolicy(1, 4, cooldown_s=0.0, hysteresis=2,
                         min_ps=1, max_ps=3,
                         budget=_RestartPolicy(max_restarts=2,
                                               window_s=60.0,
                                               backoff_s=0.0))
    load_hi = {"queue_depth": 8, "staleness_parks": 0,
               "stale_plan_drops": 0}
    assert pol.observe_ps_load(2, load_hi, n_trainers=2) is None
    assert pol.observe_ps_load(2, load_hi, n_trainers=2) == \
        ("grow_ps", None)
    # a settling migration (stale drops moving) suppresses + resets
    assert pol.observe_ps_load(
        3, {"queue_depth": 8, "staleness_parks": 0,
            "stale_plan_drops": 5}, n_trainers=2) is None
    assert pol.observe_ps_load(3, load_hi, n_trainers=2) is None
    # parks count as pressure too
    assert pol.observe_ps_load(
        3, {"queue_depth": 0, "staleness_parks": 3,
            "stale_plan_drops": 5}, n_trainers=2) is None  # drops moved
    load_idle = {"queue_depth": 0, "staleness_parks": 3,
                 "stale_plan_drops": 5}
    for _ in range(3):
        assert pol.observe_ps_load(3, load_idle, n_trainers=2) is None
    assert pol.observe_ps_load(3, load_idle, n_trainers=2) == \
        ("shrink_ps", None)
    # budget exhausted (2 actions in window): the next decision is damped
    for _ in range(5):
        pol.observe_ps_load(2, load_hi, n_trainers=2)
    assert pol._last_parks is not None
    assert pol.budget.next_delay() is None


def test_unfenced_async_journal_warns_loudly(tmp_path, capsys):
    """Satellite: the legacy per-var async path running journaled-but-
    unfenced surfaces at RUNTIME — loud stderr on the first such apply
    and an `unfenced_async` field in the stats verb — instead of living
    only in the docs."""
    ps = _async_sparse_ps(str(tmp_path))
    ps.grad_to_shard = {"g0": 0}
    assert ps._h_stats()["unfenced_async"] is False
    ps._h_send(name="g0", value=np.ones(4, np.float32), trainer_id=0)
    err = capsys.readouterr().err
    assert "JOURNALED BUT UNFENCED" in err
    assert ps._h_stats()["unfenced_async"] is True
    # once: the second apply does not repeat the warning
    ps._h_send(name="g0", value=np.ones(4, np.float32), trainer_id=0)
    assert "UNFENCED" not in capsys.readouterr().err


def _migration_run(capfd, tmp_path, name, schedule=None, crash=None,
                   steps=24, supervise=False, elastic="2:3", sync=True,
                   nproc=2):
    """One supervised sparse job with (optionally) a scheduled
    pserver-set trace and (optionally) a deterministic SIGKILL inside
    the handoff.  Returns (out, losses-by-trainer, tables-by-trainer)."""
    from paddle_tpu.distributed.launch import launch_pserver

    env = dict(os.environ)
    env.update({
        "DIST_STEPS": str(steps), "DIST_STEP_SLEEP": "0.3",
        "DIST_MODEL": "sparse", "DIST_DUMP_TABLE": "1",
        "FLAGS_max_retry": "120", "JAX_PLATFORMS": "cpu",
    })
    kw = {}
    if crash:
        env["PADDLE_TPU_MIGRATE_CRASH"] = crash
        env["PADDLE_TPU_MIGRATE_CRASH_ONCE"] = str(
            tmp_path / ("%s.crashed" % name))
        kw = dict(supervise=True, restart_backoff=0.2,
                  ckpt_dir=str(tmp_path / ("%s.ckpt" % name)))
    elif supervise:
        kw = dict(supervise=True, restart_backoff=0.2,
                  ckpt_dir=str(tmp_path / ("%s.ckpt" % name)))
    if schedule:
        kw.update(elastic_pservers=elastic, pserver_schedule=schedule,
                  elastic_cooldown=1.0)
    rc = launch_pserver([_RUNNER], nproc=nproc, n_pservers=2,
                        base_env=env, sync=sync, **kw)
    out = capfd.readouterr().out
    assert rc == 0, out
    losses, tables = {}, {}
    for tag in ["trainer.%d" % i for i in range(nproc)]:
        losses[tag] = _trainer_losses(out, tag)
        tables[tag] = _table_dump(out, tag)
    return out, losses, tables


@pytest.mark.slow  # two full cluster runs; rides scripts/ci.sh's
#                    migration-chaos pass (-m "")
def test_pserver_migration_2to3to2_bit_identical(capfd, tmp_path):
    """ACCEPTANCE (tentpole E2E): a supervised 2-trainer job whose
    pserver set changes 2 -> 3 -> 2 mid-run — shard state migrating
    out to the grown server and back off it before retirement —
    completes with finite convergent losses, and both the trajectory
    AND the dumped table are BIT-IDENTICAL to a run with no migration
    at all (every round folds exactly once at exactly one owner)."""
    out_m, losses_m, tables_m = _migration_run(
        capfd, tmp_path, "mig", schedule="5:+1,11:-1", steps=40)
    assert "PSERVER MIGRATE-COMMIT" in out_m, out_m
    assert "TRAINER REPLAN" in out_m, out_m
    # the grown server adopted at least one shard...
    assert "MIGRATE-IN" in out_m, out_m
    # ...and was retired cleanly after the shrink migrated it away
    assert "PSERVER RETIRE" in out_m, out_m
    for tag in ("trainer.0", "trainer.1"):
        ls = losses_m[tag]
        assert len(ls) == 40 and np.isfinite(ls).all(), ls
        assert ls[-1] < ls[0], ls
    out_r, losses_r, tables_r = _migration_run(
        capfd, tmp_path, "ref", schedule=None, steps=40)
    assert losses_m == losses_r, (
        "migrated run's trajectory diverged from the static run:\n"
        "mig=%s\nref=%s" % (losses_m, losses_r))
    assert tables_m == tables_r, \
        "migrated run's table is not bit-identical to the static run's"


@pytest.mark.slow  # two full cluster runs per point; ci migration pass
@pytest.mark.parametrize("point", ["serialize", "ack"])
def test_migration_under_sigkill_bit_identical(capfd, tmp_path, point):
    """ACCEPTANCE (chaos E2E): SIGKILL of the SOURCE mid-serialize, or
    of the TARGET between replay and ack — the supervised respawn
    restores, the handoff rides out the kill (RPC-layer replay +
    recovery commit), and the run's losses AND dumped table are
    BIT-IDENTICAL to the unkilled migrated run.

    Runs in the journal-armed ASYNC configuration (the PR 8 discipline
    this PR reuses as the handoff transport): every applied update is
    fsync'd before its ack, so the killed server restores EXACTLY —
    journal discipline, not snapshot luck.  (Sync mode keeps its
    pre-existing, documented one-round background-snapshot window —
    lost_rounds — which is orthogonal to the handoff protocol and
    tolerated there.)  The trace shrinks 2 -> 1, which MOVES a sparse
    shard (s % n_live) and the dense blocks off the retiring server —
    the kill lands inside that handoff."""
    out_k, losses_k, tables_k = _migration_run(
        capfd, tmp_path, "kill" + point, schedule="5:-1", steps=30,
        crash=point, sync=False, nproc=1, elastic="1:2")
    assert "PSERVER MIGRATE-CRASH point=%s" % point in out_k, out_k
    out_r, losses_r, tables_r = _migration_run(
        capfd, tmp_path, "nokill" + point, schedule="5:-1", steps=30,
        supervise=True, sync=False, nproc=1, elastic="1:2")
    assert "PSERVER MIGRATE-COMMIT" in out_r, out_r
    assert losses_k == losses_r, (
        "killed-during-migration run diverged:\nkill=%s\nref=%s"
        % (losses_k, losses_r))
    assert tables_k == tables_r, \
        "killed run's table is not bit-identical to the unkilled run's"


@pytest.mark.slow  # one cluster run; ci migration pass
def test_double_migration_flap_under_budget(capfd, tmp_path):
    """A grow immediately followed by a shrink (membership flap) rides
    the same two-phase machinery back-to-back under the action budget:
    both handoffs complete, every round still folds exactly once, and
    the job stays bit-identical to a static run."""
    out_f, losses_f, tables_f = _migration_run(
        capfd, tmp_path, "flap", schedule="5:+1,7:-1", steps=32)
    out_r, losses_r, tables_r = _migration_run(
        capfd, tmp_path, "flapref", schedule=None, steps=32)
    assert losses_f == losses_r, (
        "flap run diverged:\nflap=%s\nref=%s" % (losses_f, losses_r))
    assert tables_f == tables_r


@pytest.mark.slow  # two jax subprocess boots; ci migration pass
def test_elastic_collective_resize_2to4_matches_fresh_run():
    """ACCEPTANCE (elastic collective): --elastic is accepted in
    collective mode — a mid-run resize 2 -> 4 virtual devices re-traces
    over the new dp mesh, and the post-resize losses match a fresh 4-device run at
    rtol 1e-5 (the mean gradient is split-invariant)."""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PADDLE_TRAINING_ROLE":
                "TRAINER", "DIST_MODE": "collective", "DIST_STEPS": "6"})
    env.pop("XLA_FLAGS", None)

    def run(extra):
        e = dict(env)
        e.update(extra)
        p = subprocess.run([sys.executable, "-u", _RUNNER], env=e,
                           stdout=subprocess.PIPE,
                           stderr=subprocess.STDOUT, timeout=300)
        out = p.stdout.decode("utf-8", "replace")
        assert p.returncode == 0, out
        for ln in out.splitlines():
            if ln.startswith("LOSSES "):
                return out, json.loads(ln[len("LOSSES "):])
        raise AssertionError("no LOSSES line:\n%s" % out)

    out_r, resized = run({"DIST_COLLECTIVE_DEVICES": "2",
                          "DIST_RESIZE": "3:4"})
    assert "COLLECTIVE RESIZE step=3 nranks=4" in out_r, out_r
    _, fresh = run({"DIST_COLLECTIVE_DEVICES": "4"})
    np.testing.assert_allclose(resized, fresh, rtol=1e-5)


def test_launch_accepts_collective_elastic_single_process(monkeypatch):
    """`--elastic` is no longer rejected in collective mode: a
    single-process launch threads the resize config to the trainer
    (DIST_COLLECTIVE_ELASTIC / _SCHEDULE); multi-process meshes still
    refuse with the relaunch guidance."""
    from paddle_tpu.distributed import launch as launch_mod

    seen = {}

    def fake_collective(script_argv, nproc, base_env=None,
                        chaos_kills=None, n_pservers=0):
        seen["env"] = dict(base_env or {})
        seen["nproc"] = nproc
        return 0

    monkeypatch.setattr(launch_mod, "launch_collective", fake_collective)
    rc = launch_mod.main(["--mode", "collective", "--nproc", "1",
                          "--elastic", "2:4", "--elastic-schedule",
                          "3:+2", "x.py"])
    assert rc == 0
    assert seen["env"]["DIST_COLLECTIVE_ELASTIC"] == "2:4"
    assert seen["env"]["DIST_COLLECTIVE_SCHEDULE"] == "3:+2"
    with pytest.raises(SystemExit):
        launch_mod.main(["--mode", "collective", "--nproc", "2",
                         "--elastic", "2:4", "x.py"])
    with pytest.raises(ValueError):
        # pserver-schedule without the elastic-pservers range: loud
        launch_mod.launch_pserver(["x.py"], 1, 1,
                                  pserver_schedule="1:+1")


# ---------------------------------------------------------------------------
# async dense buckets across a plan flip (the closed PR 15 known limit)
# ---------------------------------------------------------------------------

def test_async_dense_stale_drop_echoes_victim_and_fence():
    """Server side of the dense-resend contract: a migrated-away shard
    under a pre-flip dispatch is dropped (never applied, never
    journaled) with the victim `dropped_aseq` echoed; dup and applied
    replies name the DENSE fence explicitly (`dense_acked`); and an
    EMPTY bucket at a dropped aseq is the hole-filler that unsticks the
    contiguous fence."""
    ps = ParameterServer([None], {"g0": 0}, num_trainers=1,
                         sync_mode=False,
                         plan_spec=_mig_spec(["10.9.9.7:1"]))
    applied = []
    ps._apply_shard = lambda idx, feed: applied.append(sorted(feed))
    r = ps._h_send_bucket({"g0": np.ones(2, np.float32)}, trainer_id=0,
                          seq_total=None, aseq=1)
    assert r["ok"] and r["dense_acked"] == 1 and r["acked"] == 1
    # at-least-once re-delivery: dropped, fence named for the pruner
    r = ps._h_send_bucket({"g0": np.ones(2, np.float32)}, trainer_id=0,
                          seq_total=None, aseq=1)
    assert r.get("dup") and r["dense_acked"] == 1
    # stale shard: dropped loudly with the victim aseq echoed
    r = ps._h_send_bucket({"g0.gone": np.ones(2, np.float32)},
                          trainer_id=0, seq_total=None, aseq=2)
    assert r.get("stale_plan") and r["dropped_aseq"] == 2
    assert ps.counters["stale_plan_drops"] == 1
    assert applied == [["g0"]], "stale bucket leaked into a shard"
    # the drop left a fence hole: aseq 3 applies but the contiguous
    # high-water stays at 1...
    r = ps._h_send_bucket({"g0": np.ones(2, np.float32)}, trainer_id=0,
                          seq_total=None, aseq=3)
    assert r["ok"] and r["dense_acked"] == 1
    # ...until the hole-filler (an EMPTY no-op bucket re-committing the
    # dropped aseq on this stream) lands and the fence jumps past both
    r = ps._h_send_bucket({}, trainer_id=0, seq_total=None, aseq=2)
    assert r["ok"] and r["dense_acked"] == 3


def test_async_dense_resend_prunes_on_dense_ack_and_collects_drops():
    """Client side, drain half: `dense_acked` in any drained reply
    prunes the udense resend queue up to the high-water (contiguous
    fence only), and a `stale_plan` reply carrying `dropped_aseq` lands
    in the endpoint's adropped set for the replay pass."""
    from paddle_tpu.ops import dist_ops

    dist_ops.reset_fences()
    ep = "10.9.9.8:1"
    try:
        st = dist_ops._async_st(ep)
        st["udense"] = {q: {"w.block0": np.full(2, float(q))}
                        for q in (1, 2, 3, 5)}

        class _P:
            def __call__(self, _ep):
                return self

            def drain(self):
                return [{"ok": True, "dense_acked": 3},
                        {"ok": True, "stale_plan": True,
                         "dropped_aseq": 5, "pepoch": 1}]

        stale = set()
        dist_ops._drain_plan_checked(_P(), ep, 0, stale_plan=stale)
        assert sorted(st["udense"]) == [5], "prune must stop at the fence"
        assert stale == {ep} and st["adropped"] == {5}
    finally:
        dist_ops.reset_fences()


def test_plan_flip_reships_only_dropped_dense_buckets():
    """ACCEPTANCE (satellite): the plan-flip replay re-ships EXACTLY
    the buckets the server reported dropped — regrouped by their new
    owner under the derived plan, fresh aseqs on the new owners'
    streams, the ORIGINAL aseq kept on the old endpoint (the hole
    filler) — and applied-but-unacked buckets are never re-shipped
    (that would bypass the dedup fence and double-apply)."""
    from paddle_tpu.ops import dist_ops

    dist_ops.reset_fences()
    old_ep, new_ep = "10.9.9.10:1", "10.9.9.11:1"
    try:
        st = dist_ops._async_st(old_ep)
        a0 = np.full(4, 1.0, np.float32)
        a1 = np.full(4, 2.0, np.float32)
        a2 = np.full(4, 3.0, np.float32)
        # aseq 1 was REPORTED dropped; aseq 2 is applied-but-unacked
        st["udense"] = {1: {"w.block0": a0, "w.block1": a1},
                        2: {"w.block2": a2}}
        st["adropped"] = {1}
        # the freshly derived plan moved w.block0 to the new owner and
        # kept w.block1 on the old one
        plan_rt = {"derived": {"send_buckets": [
            [new_ep, [[0, 0, 4, "w.block0"]]],
            [old_ep, [[1, 0, 4, "w.block1"]]],
        ]}}
        pipe = _StubPipe()
        n = dist_ops._async_replay_dense(pipe, plan_rt, 0, [old_ep])
        assert n == 2
        # old endpoint: the staying block under the ORIGINAL aseq
        (verb, kw), = pipe.shipped[old_ep]
        assert verb == "send_bucket" and kw["aseq"] == 1
        assert sorted(kw["blocks"]) == ["w.block1"]
        np.testing.assert_array_equal(kw["blocks"]["w.block1"], a1)
        # new owner: the moved block under a FRESH aseq on ITS stream
        (verb, kw), = pipe.shipped[new_ep]
        assert verb == "send_bucket" and kw["aseq"] == 1
        assert sorted(kw["blocks"]) == ["w.block0"]
        np.testing.assert_array_equal(kw["blocks"]["w.block0"], a0)
        # both re-shipped buckets re-entered their udense queues (a
        # crash mid-recovery re-delivers; the fences dedup), the
        # applied-but-unacked aseq 2 was NOT touched, drops cleared
        assert sorted(st["udense"]) == [1, 2]
        assert sorted(st["udense"][1]) == ["w.block1"]
        assert sorted(dist_ops._async_st(new_ep)["udense"]) == [1]
        assert st["adropped"] == set()
    finally:
        dist_ops.reset_fences()


def test_plan_flip_hole_filler_ships_even_when_all_blocks_move():
    """When EVERY block of a dropped bucket migrates away, the old
    endpoint still receives an EMPTY bucket at the original aseq — the
    no-op commit that fills the fence hole on its stream (without it,
    the contiguous dense fence on both sides sticks forever)."""
    from paddle_tpu.ops import dist_ops

    dist_ops.reset_fences()
    old_ep, new_ep = "10.9.9.12:1", "10.9.9.13:1"
    try:
        st = dist_ops._async_st(old_ep)
        a0 = np.full(4, 7.0, np.float32)
        st["udense"] = {4: {"w.block0": a0}}
        st["adropped"] = {4}
        plan_rt = {"derived": {"send_buckets": [
            [new_ep, [[0, 0, 4, "w.block0"]]],
        ]}}
        pipe = _StubPipe()
        assert dist_ops._async_replay_dense(pipe, plan_rt, 0,
                                            [old_ep]) == 2
        (_, kw), = pipe.shipped[old_ep]
        assert kw["aseq"] == 4 and kw["blocks"] == {}
        (_, kw), = pipe.shipped[new_ep]
        assert kw["aseq"] == 1
        np.testing.assert_array_equal(kw["blocks"]["w.block0"], a0)
    finally:
        dist_ops.reset_fences()
