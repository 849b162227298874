"""Parameter-server runtime: the `listen_and_serv` service loop.

TPU-native re-design of the reference pserver
(operators/distributed_ops/listen_and_serv_op.cc — RunSyncLoop :106,
RunAsyncLoop :216): a host-side service that owns a scope of parameter /
optimizer-state *blocks* (1-D slices of the original variables, see the
distribute transpiler) and applies optimizer shard programs built by the
transpiler.  Each shard program is a tiny Program compiled once by the
regular Executor (compile-first, like everything else) — the pserver's
"optimize sub-blocks" of the reference become cached XLA CPU executables.

Sync mode round protocol (reference barrier semantics):
  1. every live trainer sends its grad blocks, then barrier("send");
     each arrival folds into a running per-grad partial sum immediately
     (overlapped with the wire — the round holds no summation loop)
  2. when all send-barriers arrive: the lr program (decay schedule) runs
     once, then the folded sums apply through ONE jitted fused call per
     optimizer group (fused_apply.py; unfusable shards keep their
     per-block executor programs)
  3. trainers issue get() for updated param blocks, then barrier("fetch")
  4. round resets
Async mode: each send applies its shard program immediately, gets are
served from the live scope, no barriers.  Durability and ordering come
from the ASYNC layer instead (docs/FAULT_TOLERANCE.md, "Durable async
sparse"): every applied sparse chunk / dense bucket is appended to a
crc-framed fsync'd write-ahead journal BEFORE its ack (rotated at each
snapshot; a restarted incarnation replays journal-after-snapshot and
loses zero applied updates, skipping a torn tail record cold);
per-sender sequence fences (_sparse_fence monotonic, _dense_fence
contiguous+ahead-set for the pipelined window) turn the client's
at-least-once re-delivery into exactly-once application across SIGKILL;
and FLAGS_async_staleness_bound parks pushes/prefetches from a trainer
running ahead of the slowest live peer until it catches up or departs.

Fault tolerance (docs/FAULT_TOLERANCE.md):
  * liveness — trainers send a ``heartbeat`` verb from a background
    sender (rpc.ensure_heartbeat); a heartbeat-TRACKED trainer that goes
    silent past FLAGS_eviction_deadline is evicted: removed from the
    live set, its unsummed grads and queued sparse rows dropped, and any
    pending barrier re-evaluates against the survivors so the round
    completes instead of deadlocking.  Trainers that never heartbeat are
    never evicted (exactly the pre-liveness behavior), and eviction runs
    in SYNC mode — plus ASYNC mode when a staleness bound is armed,
    where a dead laggard would otherwise park every fast peer forever.
  * checkpoints — atomic tmp+rename snapshots plus a crc-carrying
    manifest; a torn or corrupt snapshot is skipped on restart, never a
    crash.

Async-mode sparse slot-state approximation (ADVICE r5): tables touched
by a send advance their adam beta-pows per APPLICATION (the lazy-adam
rule); tables receiving no rows between two lr-trigger sends advance
pows / decay momentum velocity once per trigger so an unlucky shard
cannot stall forever.  The residual gap vs the sync schedule: touched
tables advance per-application rather than per-step, each trainer's own
trigger fires the catch-up (so N async trainers advance untouched
tables ~N times per global step), and a pure-sparse model (no dense
grad, hence no lr trigger) keeps the legacy per-application-only rule.
With bucketed comm (FLAGS_comm_bucket_bytes) and comm_inflight > 1, an
async step spanning several buckets per endpoint may also interleave
the lr-trigger bucket with another bucket's applications — arrival
order across the in-flight window is free.  Sync mode is unaffected:
its application order comes from the round barrier, not arrival.
"""

import struct
import threading

import numpy as np

from .. import framework
from ..core.scope import Scope

# write-ahead journal record framing (async mode, docs/FAULT_TOLERANCE.md):
# [8B big-endian payload length][4B crc32][pickled record].  A record is
# appended + fsync'd BEFORE the apply's reply leaves the server, so an
# acked update is durable by construction; a kill mid-append leaves a
# truncated/corrupt TAIL that restore skips cold (counted), exactly like
# a corrupt snapshot — the unacked update is re-shipped by the client.
_J_HEAD = struct.Struct(">QI")
# cap a single journal record's claimed length (corrupt headers must not
# allocate gigabytes); generous vs any real chunk/bucket
_J_MAX_RECORD = 1 << 31
# pure-sparse async streams never bump the dense round counter, so the
# journal would grow unbounded between snapshots: force a snapshot (and
# with it a journal rotation) every this many appended records
_J_ROTATE_RECORDS = 512


class ParameterServer:
    """Service object plugged into rpc.VarServer."""

    def __init__(
        self,
        shard_programs,
        grad_to_shard,
        lr_program=None,
        num_trainers=1,
        sync_mode=True,
        scope=None,
        sparse_tables=None,
        sparse_lr=0.01,
        checkpoint_dir=None,
        checkpoint_every=1,
        server_idx=0,
        eviction_deadline=None,
        staleness_bound=None,
        plan_spec=None,
        endpoint=None,
        ps_world=None,
        sparse_shard_idx=None,
    ):
        from ..executor import Executor
        from ..places import CPUPlace

        self.shard_programs = shard_programs  # list[Program]
        self.grad_to_shard = grad_to_shard  # grad block name -> shard idx
        self.lr_program = lr_program
        self.num_trainers = num_trainers
        self.sync_mode = sync_mode
        self.scope = scope if scope is not None else Scope()
        self.exe = Executor(CPUPlace())
        # sparse embedding shards: shard name -> dict with "tbl" (2-D
        # np.ndarray), "lr" (constant fallback), "opt" ({type, attrs,
        # lr_name, lr_scale}) and lazily-created slot state (moment*,
        # beta*_pow).  Rows here belong to this server (global row g ->
        # server g%N at local index g//N); id routing is client-side, we
        # see local ids.  Legacy (tbl, lr) tuples are normalized.
        self.sparse_tables = {
            k: (v if isinstance(v, dict) else {"tbl": v[0], "lr": v[1]})
            for k, v in dict(sparse_tables or {}).items()
        }
        self.sparse_lr = sparse_lr  # fallback for tables without own lr
        # sync mode queues sparse grads and applies them at round time,
        # AFTER the lr_program run — exactly the reference's
        # optimizer-sub-block-at-barrier semantics (async applies on
        # arrival with the current lr).  Keyed (trainer_id, table) so a
        # fenced replay after a pserver restart overwrites rather than
        # double-queues (each trainer ships at most one chunk per table
        # per step — see ops/dist_ops.py _send_sparse)
        self._pending_sparse = {}

        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        # async mode: run the lr (decay) program once per logical trainer
        # step, not once per grad-var send — trigger it on a single
        # designated grad so a k-param model doesn't advance the schedule's
        # step counter k times per step
        self._lr_trigger = min(grad_to_shard) if grad_to_shard else None
        self._pending = {}  # grad block name -> {trainer_id: np.ndarray}
        # incremental fold: each trainer's dense contribution is added
        # into a running per-grad partial sum AT ARRIVAL (overlapped with
        # the wire) so _run_round no longer sums per-trainer temps while
        # holding the round lock.  _pending stays the authoritative
        # per-trainer record — overwrites (fenced replays) and evictions
        # rebuild the affected partials from it, in arrival order, so
        # the fold is bit-identical to the old round-time sum.
        self._partial = {}  # grad block name -> running sum ndarray
        # jitted fused optimize path (fused_apply.py), built lazily at
        # the first round so in-process tests with stub shard programs
        # never pay (or crash on) the analysis
        self._fused = None
        self._fused_ready = False
        self._send_barriers = set()
        self._fetch_barriers = set()
        # folded-barrier bookkeeping (bucketed wire path): how many of a
        # trainer's declared per-step buckets this server has seen
        self._send_bucket_counts = {}  # trainer_id -> buckets this round
        self._fetch_bucket_counts = {}
        # incarnation-fenced stream bookkeeping (docs/FAULT_TOLERANCE.md):
        # buckets carrying a (step, seq_idx) pair are counted by SET so a
        # fenced replay after a pserver restart is idempotent — a
        # re-delivered bucket overwrites its keyed pending slot and cannot
        # advance the fold count twice.  _folded_send/_folded_fetch record
        # the last step token each trainer FOLDED; they ride the
        # checkpoint snapshot, so after a restore they fence exactly the
        # rounds the restored params already contain (replays of those
        # rounds are dropped, in-flight rounds are re-assembled).
        self._send_step = {}     # tid -> step token being assembled
        self._send_seen = {}     # tid -> set of seq_idx seen for that step
        self._fetch_step = {}
        self._fetch_seen = {}
        self._folded_send = {}   # tid -> last folded send step (ckpt'd)
        self._folded_fetch = {}  # tid -> last folded fetch step (ckpt'd)
        self._pending_joins = set()  # tids waiting for a round boundary
        self._round = 0  # bumped after each optimize step
        self._params_ready = not sync_mode
        # liveness: the explicit live set replaces the old bare count so
        # eviction can target ONE trainer's pending state.  _tracked maps
        # heartbeat-reporting trainers to their last-contact time; only
        # tracked trainers are ever evicted (no heartbeats => the exact
        # pre-liveness behavior, nothing times out).
        self._live = set(range(num_trainers))
        self._tracked = {}  # trainer_id -> time.monotonic() of last contact
        self._evicted = set()
        self._completed = set()  # clean departures (dedups repeat completes)
        if eviction_deadline is None:
            from ..flags import get_flag

            eviction_deadline = float(get_flag("eviction_deadline"))
        self.eviction_deadline = max(0.1, float(eviction_deadline))
        self._reaper = None
        # async mode: sparse tables touched since the last lr-trigger send
        # (per-step catch-up for rowless shards, see module docstring)
        self._async_touched = set()
        self._done = threading.Event()
        # shard checkpointing (go/pserver/service.go:346 Checkpoint +
        # LoadCheckpoint :175 capability): periodic atomic snapshots of the
        # shard scope + sparse tables, restored on restart
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = max(1, int(checkpoint_every))
        self.server_idx = int(server_idx)
        self._async_sends = 0
        self._ckpt_write_lock = threading.Lock()  # serialize writer threads
        # async crash consistency (docs/FAULT_TOLERANCE.md, async section):
        # a write-ahead journal of applied updates makes the async stream
        # replayable across SIGKILL, and per-sender sequence fences make
        # the client's at-least-once re-delivery exactly-once.
        #   _sparse_fence[(tid, table)] -> highest seq durably applied
        #     (sends are serial per trainer, so monotonic drop-if-<= is
        #     exact; gaps are legal — rowless/empty chunks are acked but
        #     not journaled, so a restored fence can sit below the
        #     client's ack high-water without breaking dedup)
        #   _dense_fence[tid] -> [contiguous fence, set of applied aseqs
        #     above it] — async dense buckets ride the pipelined window
        #     and may arrive out of order
        # Both fences ride the checkpoint snapshot AND are rebuilt by
        # journal replay, so a re-shipped chunk is dropped whether the
        # original apply landed in the snapshot or only in the journal.
        self._sparse_fence = {}
        self._dense_fence = {}
        # bounded staleness: per-trainer logical clocks derived from the
        # seq tokens; a push/prefetch from a trainer more than
        # _staleness_bound ahead of the slowest LIVE peer parks on _cv
        # until the laggard catches up or departs
        self._trainer_clock = {}
        if staleness_bound is None:
            from ..flags import get_flag

            staleness_bound = get_flag("async_staleness_bound")
        self._staleness_bound = int(staleness_bound)
        from ..flags import get_flag as _gf

        self._journal_on = bool(_gf("async_journal"))
        self._journal_seg = 0  # current segment id (rotated per snapshot)
        self._journal_f = None
        self._journal_err = False  # first append failure warns loudly once
        self._replaying = False  # journal replay must not re-journal
        self._j_recs_at_snap = 0
        self._sends_at_ckpt = 0  # dense cadence marker (post-journal)
        # stale-writer guard: two snapshot writers can land out of order;
        # an older round must never overwrite a newer snapshot (its
        # journal segments may already be deleted)
        self._ckpt_written_round = -1
        # recovery observability (bench / smoke COUNTERS evidence)
        self.counters = {"evictions": 0, "readmissions": 0,
                         "registrations": 0, "dup_round_drops": 0,
                         "lost_rounds": 0,
                         # async durability + staleness evidence
                         "dedup_drops": 0, "journal_records": 0,
                         "journal_bytes": 0, "journal_replayed": 0,
                         "journal_tail_skips": 0, "staleness_parks": 0,
                         "staleness_timeouts": 0, "parked_ms": 0.0,
                         # elastic autoscaling evidence
                         "plan_epochs": 0, "stale_plan_drops": 0}
        # elastic autoscaling (docs/FAULT_TOLERANCE.md "Elastic
        # autoscaling"): the plan epoch is bumped — at a ROUND BOUNDARY
        # in sync mode, never mid-assembly — whenever the live set
        # changes durably (eviction, admission, clean departure), so
        # trainers re-derive their comm plan for the new world and
        # stale-epoch frames are fenced like stale incarnations.  The
        # membership phase log feeds the "steps/s tracks the trainer
        # count" bench evidence.
        self._plan_epoch = 0
        self._plan_dirty = False
        import time as _time

        self._phases = []  # closed phases: {epoch, world, rounds, wall_s}
        self._phase = {"epoch": 0, "world": len(self._live),
                       "round0": 0, "t0": _time.monotonic()}
        # ---- live pserver shard migration (docs/FAULT_TOLERANCE.md
        # "Live shard migration"): the declarative plan spec lets this
        # server re-derive shard->endpoint dispatch for a changed pserver
        # world and compute which of ITS shards must move.  The handoff
        # is two-phase (migrate_begin freezes + serializes + ships to the
        # targets, which journal/fsync BEFORE acking; migrate_commit
        # adopts the new world, drops the moved state, and mints the plan
        # epoch) so the epoch provably never mints before target
        # durability — a SIGKILL of source or target mid-handoff leaves
        # the OLD assignment authoritative and loses zero applied
        # updates.
        self.plan_spec = plan_spec
        self.endpoint = endpoint
        self._ps_world = [str(e) for e in (
            ps_world or (plan_spec or {}).get("endpoints")
            or ([endpoint] if endpoint else []))]
        # sparse shard name -> BASE shard index (rows hash g % n_base;
        # the index is the shard's stable identity across migrations)
        self._sparse_shard_idx = dict(sparse_shard_idx or {})
        self._frozen = False
        self._mig = None      # in-flight migrate_begin capture
        self._mig_gen = 0     # generation: a timed-out freeze self-aborts
        # delta handoff (migrate_begin delta=True): shard -> set of row
        # ids dirtied since the UNFROZEN snapshot shipped (None value =
        # whole-table mutation, re-ship everything); None when inactive
        self._mig_dirty = None
        # adopted-state registry: shard programs / sparse specs /
        # lr_program this server acquired via migrate_in — they must ride
        # the snapshot, because a restarted server rebuilds everything
        # else from its (transpile-time) listen_and_serv attrs
        self._adopted = {"programs": {}, "sparse": {}, "lr_program": None,
                         "dropped": []}
        self._dropped_vars = set()  # migrated-away param-block var names
        # runtime-surfaced reduced-guarantee flag (the legacy per-var
        # async path is journaled but UNFENCED): set on first such apply
        self._unfenced_async = False
        self.counters.update({
            "migrations_out": 0, "migrations_in": 0, "migrate_aborts": 0,
            "migrated_bytes_out": 0, "migrated_bytes_in": 0,
            "migrated_shards_out": 0, "migrated_shards_in": 0})
        # every pserver start — cold or restored — is a new INCARNATION;
        # the number rides every rpc reply envelope so trainers can fence
        # a restart (see rpc.py incarnation registry)
        self.incarnation = self._mint_incarnation()

    def _mint_incarnation(self):
        """Monotonic per-start incarnation: a counter persisted next to
        the checkpoint when there is a durable home, else time-derived
        (still distinct across restarts).  Best-effort — fencing needs
        the number to CHANGE per start, nothing stronger."""
        import os
        import time

        if self.checkpoint_dir:
            try:
                os.makedirs(self.checkpoint_dir, exist_ok=True)
                path = os.path.join(
                    self.checkpoint_dir,
                    "pserver_%d.incarnation" % self.server_idx)
                prev = 0
                if os.path.exists(path):
                    with open(path) as f:
                        prev = int(f.read().strip() or 0)
                inc = prev + 1
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    f.write(str(inc))
                os.replace(tmp, path)
                return inc
            except (OSError, ValueError):
                pass
        return int(time.time() * 1000) & 0x7FFFFFFFFFFF

    # ---- async write-ahead journal (durable async sparse) ----------------
    def _journal_enabled(self):
        return bool(self._journal_on and self.checkpoint_dir
                    and not self.sync_mode)

    def _journal_path(self, seg):
        import os

        return os.path.join(
            self.checkpoint_dir,
            "pserver_%d.journal.seg%06d" % (self.server_idx, int(seg)))

    def _journal_segments(self):
        """Existing segment ids for this shard, sorted ascending."""
        import os
        import re

        if not self.checkpoint_dir:
            return []
        pat = re.compile(
            r"^pserver_%d\.journal\.seg(\d+)$" % self.server_idx)
        try:
            names = os.listdir(self.checkpoint_dir)
        except OSError:
            return []
        return sorted(int(m.group(1))
                      for m in (pat.match(n) for n in names) if m)

    def _journal_append_locked(self, rec):
        """Append one crc-framed record and fsync — called under the
        service lock, BEFORE the apply's reply leaves, so an acked update
        is durable.  A disk failure degrades to the old lose-on-restart
        behavior, loudly (once), rather than killing the serving loop.

        Known tradeoff: the fsync runs under the service lock, so every
        concurrent verb (reads included) stalls behind each disk sync.
        Group commit — append+flush under the lock, fsync the captured
        file object outside it before the reply — would lift that, but
        interacts with snapshot-capture rotation closing the file
        mid-sync; left as future perf work (the apply itself already
        serializes writers here)."""
        if not self._journal_enabled() or self._replaying:
            return
        import os
        import pickle
        import sys
        import zlib

        try:
            payload = pickle.dumps(rec, protocol=pickle.HIGHEST_PROTOCOL)
            frame = _J_HEAD.pack(len(payload),
                                 zlib.crc32(payload) & 0xFFFFFFFF) + payload
            if self._journal_f is None:
                os.makedirs(self.checkpoint_dir, exist_ok=True)
                self._journal_f = open(
                    self._journal_path(self._journal_seg), "ab")
            self._journal_f.write(frame)
            self._journal_f.flush()
            os.fsync(self._journal_f.fileno())
            self.counters["journal_records"] += 1
            self.counters["journal_bytes"] += len(frame)
            self._journal_err = False
        except OSError as e:
            if not self._journal_err:
                self._journal_err = True
                sys.stderr.write(
                    "PSERVER journal append failed (%s): async updates "
                    "since the last snapshot are NOT crash-durable until "
                    "the journal recovers\n" % e)

    def _journal_quarantine(self):
        """An UNUSABLE snapshot orphans its journal: the segments hold
        deltas whose base state is gone, so they can never be replayed
        correctly — and left on disk they would poison the NEXT lineage
        (the fresh writer would append into / a later restore would
        replay dead-lineage records on top of new state).  Remove them,
        loudly, and reseed the writer past their numbering."""
        import os
        import sys

        if not self._journal_enabled():
            return
        segs = self._journal_segments()
        if not segs:
            return
        sys.stderr.write(
            "PSERVER journal segments %s belong to the unusable "
            "snapshot's lineage (deltas without their base); removing "
            "them — the cold start cannot replay them\n" % segs)
        self.counters["journal_tail_skips"] += len(segs)
        for seg in segs:
            try:
                os.remove(self._journal_path(seg))
            except OSError:
                pass
        self._journal_seg = max(self._journal_seg, segs[-1] + 1)

    def _journal_rotate_locked(self):
        """Start a fresh segment (at snapshot capture): everything before
        the new segment is contained in the snapshot being taken, so once
        that snapshot lands the older segments can be deleted.  Returns
        the new segment id (the snapshot's replay-from marker)."""
        if not self._journal_enabled():
            return None
        if self._journal_f is not None:
            try:
                self._journal_f.close()
            except OSError:
                pass
            self._journal_f = None
        self._journal_seg += 1
        self._j_recs_at_snap = self.counters["journal_records"]
        return self._journal_seg

    def _journal_maybe_snapshot_locked(self):
        """Sparse-only async streams never bump the dense round counter,
        so without this the journal would grow unbounded between
        snapshots: force a snapshot (and its rotation) every
        _J_ROTATE_RECORDS appended records."""
        if (self.checkpoint_dir and not self._replaying
                and self.counters["journal_records"]
                - self._j_recs_at_snap >= _J_ROTATE_RECORDS):
            self._round += 1
            self._maybe_checkpoint()

    def _replay_journal(self, from_seg):
        """Apply journal records from segment `from_seg` on, in order,
        through the SAME application paths the live verbs use (lr
        triggers, slot state, fences and clocks all advance identically).
        A corrupt/truncated record ends ITS segment's replay (counted,
        cold — the kill landed mid-append and the unacked update will be
        re-shipped); later segments, written by later incarnations, still
        replay.  New appends then go to a segment PAST everything seen,
        so a skipped tail is never appended after."""
        if not self._journal_enabled():
            return 0
        import pickle
        import sys
        import zlib

        segs = [s for s in self._journal_segments() if s >= int(from_seg)]
        n = 0
        self._replaying = True
        try:
            for seg in segs:
                try:
                    with open(self._journal_path(seg), "rb") as f:
                        buf = f.read()
                except OSError as e:
                    sys.stderr.write(
                        "PSERVER journal seg %d unreadable (%s); "
                        "skipped\n" % (seg, e))
                    self.counters["journal_tail_skips"] += 1
                    continue
                off = 0
                while off < len(buf):
                    if off + _J_HEAD.size > len(buf):
                        self.counters["journal_tail_skips"] += 1
                        break
                    ln, crc = _J_HEAD.unpack_from(buf, off)
                    if (ln > _J_MAX_RECORD
                            or off + _J_HEAD.size + ln > len(buf)):
                        self.counters["journal_tail_skips"] += 1
                        break
                    payload = buf[off + _J_HEAD.size:
                                  off + _J_HEAD.size + ln]
                    if zlib.crc32(payload) & 0xFFFFFFFF != crc:
                        self.counters["journal_tail_skips"] += 1
                        break
                    try:
                        rec = pickle.loads(payload)
                        self._apply_journal_record(rec)
                    except Exception as e:
                        sys.stderr.write(
                            "PSERVER journal seg %d record unusable (%s); "
                            "skipping segment tail\n" % (seg, e))
                        self.counters["journal_tail_skips"] += 1
                        break
                    n += 1
                    off += _J_HEAD.size + ln
            # new appends must land in a segment future restores WILL
            # replay: past every segment seen, and never below the
            # snapshot's replay-from marker — after a snapshot that
            # deleted all covered segments, an empty journal dir must
            # not reset the writer to seg 0 (records there would sit
            # below the marker and a second restart would skip them,
            # silently losing acked updates)
            existing = self._journal_segments()
            self._journal_seg = max(
                [self._journal_seg, int(from_seg)]
                + [s + 1 for s in existing])
        finally:
            self._replaying = False
        self.counters["journal_replayed"] = n
        if n or self.counters["journal_tail_skips"]:
            print("PSERVER JOURNAL-REPLAY records=%d tail_skips=%d "
                  "segments=%s" % (n, self.counters["journal_tail_skips"],
                                   segs), flush=True)
        return n

    def _apply_journal_record(self, rec):
        kind = rec.get("k")
        tid = int(rec.get("tid", 0))
        if kind == "s":
            table = rec["t"]
            if table not in self.sparse_tables:
                import sys

                sys.stderr.write(
                    "PSERVER journal names unknown sparse table %r; "
                    "record skipped\n" % (table,))
                return
            ids = np.asarray(rec["i"])
            if ids.size:
                self._async_touched.add(table)
                self._apply_sparse(table, ids, np.asarray(rec["r"]))
            if rec.get("q") is not None:
                key = (tid, table)
                seq = int(rec["q"])
                self._sparse_fence[key] = max(
                    self._sparse_fence.get(key, 0), seq)
                self._clock_update_locked(tid, seq)
        elif kind == "d":
            aseq = rec.get("q")
            if aseq is not None and self._dense_fence_is_dup(tid, aseq):
                return
            for name in sorted(rec["b"]):
                self._apply_async_send_locked(name,
                                              np.asarray(rec["b"][name]))
            if aseq is not None:
                # aseq stays OUT of _trainer_clock (bucket units, not
                # steps — see _h_send_bucket)
                self._dense_fence_commit(tid, aseq)
        elif kind == "v":
            self._apply_async_send_locked(rec["n"], np.asarray(rec["v"]))
        # ---- live shard migration records (docs/FAULT_TOLERANCE.md
        # "Live shard migration"): state HANDED OFF from another server,
        # applied both live (migrate_in) and from journal replay — an
        # adopted shard survives the target's own SIGKILL either way
        elif kind == "mshard":
            g = str(rec["g"])
            prog = framework.Program.from_json(rec["prog"])
            si = self.grad_to_shard.get(g)
            if si is None:
                self.grad_to_shard[g] = len(self.shard_programs)
                self.shard_programs.append(prog)
            else:
                self.shard_programs[si] = prog  # idempotent retry
            for n, v in sorted(rec["vars"].items()):
                self.scope.set(n, np.ascontiguousarray(v))
                # a shard can move BACK (2 -> 3 -> 2): re-adoption
                # clears the dropped-var fence for its vars
                self._dropped_vars.discard(n)
            if g in self._adopted["dropped"]:
                self._adopted["dropped"].remove(g)
            self._adopted["programs"][g] = rec["prog"]
            self._fused = None
            self._fused_ready = False
            self._recalc_lr_trigger_locked()
        elif kind == "mtable":
            shard = str(rec["t"])
            if (self._mig_dirty is not None
                    and shard in self._mig_dirty):
                # a full table landed UNDER our own in-flight delta
                # handoff of the same shard (shard bouncing back):
                # row-level tracking is no longer sound — re-ship whole
                self._mig_dirty[shard] = None
            info = {}
            for kk, vv in rec["info"].items():
                info[kk] = (np.ascontiguousarray(vv)
                            if isinstance(vv, np.ndarray) else vv)
            info.setdefault("opt", {"type": "sgd", "attrs": {}})
            self.sparse_tables[shard] = info
            if shard in self._adopted["dropped"]:
                self._adopted["dropped"].remove(shard)
            if int(rec.get("s", -1)) >= 0:
                self._sparse_shard_idx[shard] = int(rec["s"])
            for t, sq in (rec.get("fences") or {}).items():
                key = (int(t), shard)
                self._sparse_fence[key] = max(
                    self._sparse_fence.get(key, 0), int(sq))
            self._adopted["sparse"][shard] = {
                "s": int(rec.get("s", -1)),
                "lr": info.get("lr"), "opt": info.get("opt")}
        elif kind == "mwhole":
            for n, v in sorted((rec.get("vars") or {}).items()):
                # set-if-absent: an established server's own copies (its
                # lr decay state advanced by its own rounds) win
                if self.scope.find_var(n) is None:
                    self.scope.set(n, np.ascontiguousarray(v))
            if rec.get("lr_program") and self.lr_program is None:
                self.lr_program = framework.Program.from_json(
                    rec["lr_program"])
                self._adopted["lr_program"] = rec["lr_program"]
        elif kind == "mrows":
            # delta-handoff FINAL TAIL: row-level overwrite of a table
            # whose full snapshot already landed (an earlier mtable
            # record in this handoff) — ids carry the rows dirtied
            # while the source kept serving, `scal` the non-row state
            # (adam beta pows, lr) whose final frozen values win
            shard = str(rec["t"])
            info = self.sparse_tables.get(shard)
            if info is None:
                import sys

                sys.stderr.write(
                    "PSERVER mrows names unknown sparse table %r "
                    "(no snapshot landed first); record skipped\n"
                    % (shard,))
                return
            ids = np.asarray(rec["i"]).reshape(-1).astype(np.int64)
            for kk, vv in sorted((rec.get("rows") or {}).items()):
                vv = np.asarray(vv)
                arr = info.get(kk)
                if arr is None:
                    # a moment/velocity slot first materialized AFTER
                    # the snapshot (setdefault in _apply_sparse)
                    arr = info[kk] = np.zeros_like(info["tbl"])
                if ids.size:
                    arr[ids] = vv
            for kk, vv in sorted((rec.get("scal") or {}).items()):
                info[kk] = (np.ascontiguousarray(vv)
                            if isinstance(vv, np.ndarray) else vv)
            for t, sq in (rec.get("fences") or {}).items():
                key = (int(t), shard)
                self._sparse_fence[key] = max(
                    self._sparse_fence.get(key, 0), int(sq))
        elif kind == "mfence":
            # migrated fold fences: rounds the shipped state already
            # contains must fence here exactly as at the source (sync
            # rounds are lockstep, so max-merge is exact)
            for t, s in (rec.get("send") or {}).items():
                t = int(t)
                self._folded_send[t] = max(
                    self._folded_send.get(t, -1), int(s))
            for t, s in (rec.get("fetch") or {}).items():
                t = int(t)
                self._folded_fetch[t] = max(
                    self._folded_fetch.get(t, -1), int(s))

    # ---- async delivery fences + bounded staleness -----------------------
    def _dense_fence_is_dup(self, tid, aseq):
        st = self._dense_fence.get(int(tid))
        if st is None or aseq is None:
            return False
        aseq = int(aseq)
        return aseq <= st[0] or aseq in st[1]

    def _dense_fence_commit(self, tid, aseq):
        """Contiguous fence + ahead-set: async dense buckets ride the
        pipelined window, so they may commit out of order — the fence
        advances through the set as the gaps fill, keeping the set no
        larger than the in-flight window."""
        st = self._dense_fence.setdefault(int(tid), [0, set()])
        st[1].add(int(aseq))
        while st[0] + 1 in st[1]:
            st[0] += 1
            st[1].discard(st[0])

    def _clock_update_locked(self, tid, clock):
        tid = int(tid)
        cur = self._trainer_clock.get(tid, 0)
        if int(clock) > cur:
            self._trainer_clock[tid] = int(clock)
            if not self._replaying:
                self._cv.notify_all()

    def _park_if_stale_locked(self, tid, clock):
        """Bounded staleness (async mode): hold this push/prefetch while
        its trainer runs more than _staleness_bound steps ahead of the
        slowest LIVE peer; released when the laggard's clock advances or
        it departs (complete / eviction — which is why the reaper also
        runs on async servers when the bound is armed).  The wait is
        capped: a bound must throttle, never deadlock — on timeout the
        call proceeds loudly and the timeout is counted."""
        bound = self._staleness_bound
        if bound <= 0 or self.sync_mode or self._replaying or clock is None:
            return
        import time

        tid = int(tid)
        clock = int(clock)

        def clear():
            if (self._done.is_set() or tid in self._evicted
                    or tid not in self._live):
                return True
            others = [c for t, c in self._trainer_clock.items()
                      if t != tid and t in self._live]
            return not others or clock - min(others) <= bound

        if clear():
            return
        self.counters["staleness_parks"] += 1
        print("PSERVER PARK trainer=%d clock=%d bound=%d"
              % (tid, clock, bound), flush=True)
        t0 = time.monotonic()
        limit = max(10.0, 3.0 * self.eviction_deadline)
        released = self._cv.wait_for(clear, timeout=limit)
        self.counters["parked_ms"] = round(
            self.counters["parked_ms"]
            + (time.monotonic() - t0) * 1e3, 3)
        if not released:
            self.counters["staleness_timeouts"] += 1
            print("PSERVER STALENESS-TIMEOUT trainer=%d clock=%d: laggard "
                  "made no progress in %.0fs; releasing the park rather "
                  "than deadlocking" % (tid, clock, limit), flush=True)

    # ---- checkpoint (fault tolerance) -----------------------------------
    def _ckpt_path(self, dir=None):
        import os

        return os.path.join(
            dir or self.checkpoint_dir, "pserver_%d.ckpt" % self.server_idx
        )

    def _snapshot(self):
        """Copy shard state (called under the service lock; numpy copies so
        later in-place updates can't tear the snapshot)."""
        return {
            "round": self._round,
            # async delivery fences + clocks ride the snapshot like the
            # sync fold fences do: a restored server must drop re-shipped
            # chunks whose applies are INSIDE the restored state
            "async_seq": {
                "sparse": dict(self._sparse_fence),
                "dense": {t: [st[0], sorted(st[1])]
                          for t, st in self._dense_fence.items()},
                "clock": dict(self._trainer_clock)},
            # journal rotation: records before this segment are contained
            # in THIS snapshot; restore replays segments >= it, and the
            # writer deletes segments < it once the snapshot lands
            "journal_seg": self._journal_rotate_locked(),
            # the plan epoch rides the snapshot: a restored server must
            # not fall behind its trainers' epochs (its stale fence
            # would misread every current-epoch frame as the future)
            "plan": {"epoch": self._plan_epoch},
            # live shard migration: the current pserver world plus every
            # shard program / sparse spec ADOPTED via migrate_in — a
            # restarted server rebuilds everything else from its
            # transpile-time listen_and_serv attrs, but adopted shards
            # exist only here (and in the journal), and dropped shards
            # must not be resurrected from those same attrs
            "migration": {
                "world": list(self._ps_world),
                "programs": dict(self._adopted["programs"]),
                "sparse": {k: dict(v) for k, v in
                           self._adopted["sparse"].items()},
                "lr_program": self._adopted["lr_program"],
                "dropped": list(self._adopted["dropped"]),
                "dropped_vars": sorted(self._dropped_vars),
                "shard_idx": dict(self._sparse_shard_idx)},
            # per-trainer fold fences ride the SAME snapshot as the
            # params: after a restore, replayed buckets for rounds the
            # restored state already contains are dropped, rounds the
            # snapshot missed are re-assembled (incarnation fencing)
            "folded": {"send": dict(self._folded_send),
                       "fetch": dict(self._folded_fetch)},
            # departed trainers ride the snapshot too: a restored sync
            # server must not rebuild its live set around ghosts it
            # already evicted — their folds would never arrive and every
            # restored barrier would hang (register still readmits them).
            # The LIVE set rides as well: an elastic-grown rank (>= the
            # transpile-time trainer count) is otherwise forgotten by a
            # restart's range(num_trainers) reconstruction, and the
            # restored server would declare the job done under it the
            # moment the original ranks complete
            "departed": {"evicted": sorted(self._evicted),
                         "completed": sorted(self._completed),
                         "live": sorted(self._live)},
            "vars": {
                n: np.array(self.scope.get(n))
                for n in self.scope.local_var_names()
            },
            "sparse": {
                k: {
                    kk: (np.array(vv) if isinstance(vv, np.ndarray) else vv)
                    for kk, vv in info.items()
                    if kk == "tbl"
                    or kk.startswith(("moment", "beta", "velocity"))
                }
                for k, info in self.sparse_tables.items()
            },
        }

    def _manifest_path(self, dir=None):
        import os

        return os.path.join(
            dir or self.checkpoint_dir,
            "pserver_%d.manifest.json" % self.server_idx,
        )

    def _write_snapshot(self, data, dir=None):
        """Atomic write-tmp + rename (the Go pserver's crc+rename
        discipline, service.go:346); runs OFF the service lock.  `dir`
        overrides the server's own checkpoint_dir for trainer-requested
        snapshots.  A crc-carrying manifest lands (atomically) AFTER the
        snapshot: restore verifies the crc, so silent corruption is
        detected; a crash between the two renames leaves a stale manifest
        over a complete snapshot, which restore recognizes and repairs
        (see load_checkpoint)."""
        import json
        import os
        import pickle
        import zlib

        target = dir or self.checkpoint_dir
        own_home = target == self.checkpoint_dir
        os.makedirs(target, exist_ok=True)
        path = self._ckpt_path(dir=target)
        tmp = path + ".tmp"
        with self._ckpt_write_lock:
            if own_home:
                # stale-writer guard: background writers can land out of
                # order, and an older round must never overwrite a newer
                # snapshot — its journal segments may already be gone
                rnd = int(data.get("round", 0))
                if rnd < self._ckpt_written_round:
                    return
                self._ckpt_written_round = rnd
            payload = pickle.dumps(data, protocol=pickle.HIGHEST_PROTOCOL)
            with open(tmp, "wb") as f:
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            manifest = {
                "round": int(data.get("round", 0)),
                "file": os.path.basename(path),
                "nbytes": len(payload),
                "crc32": zlib.crc32(payload) & 0xFFFFFFFF,
                "server_idx": self.server_idx,
                # async journal rotation point: restore replays journal
                # segments >= this (absent/None for sync snapshots) —
                # observability for operators and the chaos fences
                "journal_seg": data.get("journal_seg"),
            }
            mtmp = self._manifest_path(dir=target) + ".tmp"
            with open(mtmp, "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(mtmp, self._manifest_path(dir=target))
            # the snapshot is durable: journal segments it contains are
            # no longer needed for replay (crash BEFORE this point keeps
            # them, so the previous snapshot still has its full tail)
            jseg = data.get("journal_seg")
            if own_home and jseg is not None:
                for seg in self._journal_segments():
                    if seg < int(jseg):
                        try:
                            os.remove(self._journal_path(seg))
                        except OSError:
                            pass

    def save_checkpoint(self, dir=None):
        if not (dir or self.checkpoint_dir):
            return False
        self._write_snapshot(self._snapshot(), dir=dir)
        return True

    def load_checkpoint(self):
        """Restore shard state from the latest snapshot; returns the
        restored round, or None when no (usable) checkpoint exists.  A
        corrupt / truncated snapshot is reported and SKIPPED — a
        restarting pserver must come up (cold) rather than crash-loop on
        a bad file.  A crc MISMATCH alone is not fatal when the snapshot
        itself parses cleanly: a kill between the snapshot rename and the
        manifest rename leaves a STALE manifest next to a complete,
        atomically-renamed snapshot — that window must stay recoverable
        (the manifest is rewritten to match)."""
        if not self.checkpoint_dir:
            return None
        import json
        import os
        import pickle
        import sys
        import zlib

        path = self._ckpt_path()
        if not os.path.exists(path):
            # no snapshot ever landed: the journal (never rotated without
            # one) holds the ENTIRE applied-update history since birth —
            # replaying it from segment 0 is a full recovery
            if self._replay_journal(0):
                return self._round
            return None
        try:
            with open(path, "rb") as f:
                payload = f.read()
            mpath = self._manifest_path()
            crc_note = None
            if os.path.exists(mpath):
                try:
                    with open(mpath) as f:
                        manifest = json.load(f)
                    crc = zlib.crc32(payload) & 0xFFFFFFFF
                    if (len(payload) != int(manifest["nbytes"])
                            or crc != int(manifest["crc32"])):
                        crc_note = (
                            "manifest says %s bytes crc %08x, file is %d "
                            "bytes crc %08x" % (manifest["nbytes"],
                                                int(manifest["crc32"]),
                                                len(payload), crc))
                except (ValueError, KeyError, OSError) as e:
                    crc_note = "manifest unreadable: %s" % e
            else:
                crc_note = "no manifest (pre-manifest-era checkpoint)"
            data = pickle.loads(payload)
            if not (isinstance(data, dict) and "vars" in data):
                raise ValueError("snapshot has no vars table")
        except Exception as e:
            sys.stderr.write(
                "PSERVER checkpoint %s unusable, starting cold: %s\n"
                % (path, e))
            self._journal_quarantine()
            return None
        # legacy bare-array sparse entries (pre-slot-state checkpoints):
        # upgrade in the loaded data itself so the rewrite below lands a
        # MODERN snapshot + crc manifest on disk
        sparse = data.get("sparse", {})
        legacy = any(not isinstance(v, dict) for v in sparse.values())
        if legacy:
            data = dict(data)
            data["sparse"] = {
                k: (v if isinstance(v, dict)
                    else {"tbl": np.ascontiguousarray(v)})
                for k, v in sparse.items()}
        if crc_note is not None or legacy:
            # stale/missing manifest (crash landed between the two
            # renames, or a pre-manifest/legacy-format checkpoint) over a
            # snapshot that parses cleanly: recover, rewrite both files
            # in the modern format
            sys.stderr.write(
                "PSERVER checkpoint %s: %s; snapshot parsed cleanly — "
                "restoring and rewriting snapshot + manifest\n"
                % (path, crc_note or "legacy sparse format"))
            try:
                self._write_snapshot(data)
            except OSError:
                pass
        # live shard migration: re-adopt handed-off shards BEFORE the
        # vars/sparse restore (the sparse loop skips tables this server
        # doesn't know), and re-drop migrated-away shards the transpile-
        # time attrs would otherwise resurrect into double ownership
        mig = data.get("migration") or {}
        if mig.get("world"):
            self._ps_world = [str(e) for e in mig["world"]]
        self._sparse_shard_idx.update(
            {str(k): int(v)
             for k, v in (mig.get("shard_idx") or {}).items()})
        for g, pj in sorted((mig.get("programs") or {}).items()):
            if g not in self.grad_to_shard:
                self.grad_to_shard[g] = len(self.shard_programs)
                self.shard_programs.append(framework.Program.from_json(pj))
            self._adopted["programs"][g] = pj
        for shard, spec in sorted((mig.get("sparse") or {}).items()):
            if shard not in self.sparse_tables:
                self.sparse_tables[shard] = {
                    "tbl": np.zeros((0, 1), np.float32),  # data["sparse"]
                    "lr": spec.get("lr"),                 # fills it below
                    "opt": spec.get("opt") or {"type": "sgd",
                                               "attrs": {}}}
            if int(spec.get("s", -1)) >= 0:
                self._sparse_shard_idx[shard] = int(spec["s"])
            self._adopted["sparse"][shard] = dict(spec)
        if mig.get("lr_program") and self.lr_program is None:
            self.lr_program = framework.Program.from_json(
                mig["lr_program"])
            self._adopted["lr_program"] = mig["lr_program"]
        self._dropped_vars |= set(mig.get("dropped_vars") or [])
        for name in mig.get("dropped") or []:
            si = self.grad_to_shard.pop(name, None)
            if si is not None:
                self.shard_programs[si] = None
                self._fused = None
                self._fused_ready = False
            self.sparse_tables.pop(name, None)
            if name not in self._adopted["dropped"]:
                self._adopted["dropped"].append(name)
        self._recalc_lr_trigger_locked()
        for n, v in data["vars"].items():
            self.scope.set(n, v)
        for k, v in data["sparse"].items():
            if k not in self.sparse_tables:
                continue
            info = self.sparse_tables[k]
            for kk, vv in v.items():
                info[kk] = (np.ascontiguousarray(vv)
                            if isinstance(vv, np.ndarray) else vv)
        self._round = int(data.get("round", 0))
        folded = data.get("folded") or {}
        self._folded_send = {int(t): int(s)
                             for t, s in (folded.get("send") or {}).items()}
        self._folded_fetch = {int(t): int(s)
                              for t, s in (folded.get("fetch") or {}).items()}
        departed = data.get("departed") or {}
        self._evicted |= {int(t) for t in departed.get("evicted", [])}
        self._completed |= {int(t) for t in departed.get("completed", [])}
        # elastic ranks the dead incarnation had admitted (absent in
        # pre-elastic snapshots: range(num_trainers) stays the base)
        self._live |= {int(t) for t in departed.get("live", [])}
        plan = data.get("plan") or {}
        self._plan_epoch = max(self._plan_epoch,
                               int(plan.get("epoch", 0)))
        import time as _time

        # the open phase restarts at THIS incarnation's round/clock: the
        # dead incarnation already reported its rounds in its own stats,
        # and carrying round0=0 forward would double-count every
        # pre-restart round in the next closed phase (corrupting the
        # steps/s-per-membership evidence)
        self._phase.update(epoch=self._plan_epoch, round0=self._round,
                           t0=_time.monotonic())
        self._live -= (self._evicted | self._completed)
        self._phase["world"] = len(self._live)
        if not self._live:
            # everyone the snapshot knew is gone: nothing left to serve
            # (a rejoin would re-arm via register/_admit_locked)
            self._done.set()
        if self.sync_mode and self._round > 0:
            # the restored params ARE a completed round's output: serve
            # them.  Leaving params_ready False would park every
            # replaying get on a flag only the NEXT round sets — a
            # restart during the fetch phase would deadlock the job.
            self._params_ready = True
        # async delivery fences + clocks: restore from the snapshot, then
        # let journal replay advance them past it
        aseq = data.get("async_seq") or {}
        self._sparse_fence = {
            (int(t), str(tb)): int(s)
            for (t, tb), s in (aseq.get("sparse") or {}).items()}
        self._dense_fence = {
            int(t): [int(st[0]), set(int(x) for x in st[1])]
            for t, st in (aseq.get("dense") or {}).items()}
        self._trainer_clock = {
            int(t): int(c) for t, c in (aseq.get("clock") or {}).items()}
        jseg = data.get("journal_seg")
        if jseg is not None:
            # the snapshot coordinated with the journal: replay the
            # segments it does not contain — zero applied updates lost
            self._replay_journal(int(jseg))
        return self._round

    def _maybe_checkpoint(self):
        """Called under the service lock: snapshot cheaply here, serialize
        + write on a background thread so trainer RPCs never stall on disk."""
        if not (self.checkpoint_dir and self._round % self.checkpoint_every == 0):
            return
        try:
            data = self._snapshot()
        except Exception:
            import traceback

            traceback.print_exc()
            return

        def write():
            try:
                self._write_snapshot(data)
            except Exception:
                import traceback

                traceback.print_exc()

        threading.Thread(target=write, daemon=True).start()

    # ---- liveness / eviction --------------------------------------------
    def _touch(self, trainer_id):
        """Any verb from a tracked trainer counts as contact — a trainer
        mid-barrier is provably alive even if a heartbeat got delayed."""
        import time

        tid = int(trainer_id)
        if tid in self._tracked:
            self._tracked[tid] = time.monotonic()

    def _h_heartbeat(self, trainer_id=0):
        import time

        with self._cv:
            tid = int(trainer_id)
            live = tid in self._live
            if live:
                # first beat makes the trainer evictable from here on
                self._tracked[tid] = time.monotonic()
                self._ensure_reaper_locked()
            # an evicted trainer is NOT re-admitted: its grads were
            # dropped mid-round, re-joining would corrupt barrier math —
            # it learns it is dead from live=False and should exit
            return self._plan_reply_locked(
                {"ok": True, "live": live, "round": self._round})

    def _h_evict(self, trainer_id=0, respawn=False):
        """Out-of-band death report (the launcher's supervisor role): a
        trainer that died before its first heartbeat was never tracked,
        so the reaper can't see it — whoever reaped the process tells us.
        Unlike `complete`, this drops the ghost's pending grads / queued
        sparse rows and stale barrier entries (the full _evict_locked
        cleanup), so a partial round contribution never leaks.

        `respawn=True` (a supervised child: its replacement IS coming)
        parks the id as a pending join BEFORE the eviction, so the
        eviction's own boundary re-check readmits it — without this, the
        sole trainer's death would empty the live set and declare the
        job done while the supervisor is still booting the replacement,
        and the exiting pserver would strand that replacement forever."""
        with self._cv:
            tid = int(trainer_id)
            if respawn:
                # parked in BOTH modes: async has no barriers, so the
                # boundary check admits immediately — but without the
                # park an async sole-trainer death would still empty the
                # live set and exit the pserver under the replacement
                self._pending_joins.add(tid)
            else:
                # TERMINAL evict (restart budget exhausted, or a policy
                # retirement): the id is never coming back — unpark any
                # earlier respawn-optimistic report so the server does
                # not keep the job alive for a replacement that will
                # never boot
                self._pending_joins.discard(tid)
            self._evict_locked(tid, "reported dead")
            # _evict_locked early-returns for an id not in the live set
            # (already evicted / completed): a parked respawn join must
            # still admit if the server sits at a boundary
            self._admit_pending_joins_locked()
            if not respawn and not self._live and not self._pending_joins:
                # the terminal evict emptied the world: the job is over
                # NOW, not at the eviction deadline
                self._done.set()
                self._cv.notify_all()
            return {"ok": True, "live": len(self._live)}

    def _ensure_reaper_locked(self):
        # eviction is historically a SYNC-mode concept: async mode has no
        # barrier a ghost can hang, and evicting a merely-partitioned
        # async trainer would reject its (harmless) updates when it
        # heals.  With a staleness bound ARMED, async grows the same
        # liveness dependency — a dead laggard would park every fast peer
        # forever — so the reaper runs there too (eviction frees the
        # bound, preserving the PR 1 progress guarantee).
        if (self._reaper is not None or self._done.is_set()
                or not (self.sync_mode or self._staleness_bound > 0)):
            return
        t = threading.Thread(target=self._reaper_loop, daemon=True,
                             name="pserver-reaper-%d" % self.server_idx)
        self._reaper = t
        t.start()

    def _reaper_loop(self):
        """Evict tracked trainers that miss the deadline.  Polls at a
        fraction of the deadline so eviction lands within ~1.25x of it.
        One eviction's round re-evaluation failing must not kill the
        reaper — a dead reaper silently re-introduces the barrier
        deadlock this thread exists to break."""
        import time

        period = max(0.05, self.eviction_deadline / 4.0)
        while not self._done.wait(period):
            try:
                with self._cv:
                    now = time.monotonic()
                    dead = [
                        t for t, seen in self._tracked.items()
                        if t in self._live
                        and now - seen > self.eviction_deadline
                    ]
                    for t in dead:
                        self._evict_locked(
                            t, "missed liveness deadline (%.1fs)"
                            % self.eviction_deadline)
            except Exception:
                import traceback

                traceback.print_exc()

    def _clear_round_state_locked(self, tid):
        """Drop one trainer's partial contribution to the CURRENT round:
        unsummed dense grads, queued sparse rows, stale barrier entries
        and in-progress bucket-stream counts.  Shared by eviction (the
        ghost's state must not leak) and re-registration (a fresh trainer
        incarnation restarts its stream from scratch)."""
        for gname, per_trainer in self._pending.items():
            if per_trainer.pop(tid, None) is not None:
                # the ghost's grads were already folded into the running
                # partial: rebuild that grad's sum from the survivors
                # (in arrival order — same float result as a fresh fold)
                self._refold_partial_locked(gname)
        # prune grads left with NO contributors: an empty inner dict
        # would keep _mid_round_locked() True forever, so the round
        # boundary (and with it every parked rejoin) would never arrive
        self._pending = {g: per for g, per in self._pending.items() if per}
        self._partial = {g: t for g, t in self._partial.items()
                         if g in self._pending}
        self._pending_sparse = {
            k: v for k, v in self._pending_sparse.items() if k[0] != tid
        }
        self._send_barriers.discard(tid)
        self._fetch_barriers.discard(tid)
        self._send_bucket_counts.pop(tid, None)
        self._fetch_bucket_counts.pop(tid, None)
        self._send_step.pop(tid, None)
        self._send_seen.pop(tid, None)
        self._fetch_step.pop(tid, None)
        self._fetch_seen.pop(tid, None)

    def _refold_partial_locked(self, gname):
        """Recompute one grad's running partial from its per-trainer
        record (rare paths only: eviction, a fenced replay overwriting a
        slot).  Insertion order == arrival order, so the rebuilt sum is
        float-identical to an uninterrupted incremental fold."""
        total = None
        for v in self._pending.get(gname, {}).values():
            total = v if total is None else total + v
        if total is None:
            self._partial.pop(gname, None)
        else:
            self._partial[gname] = total

    def _fold_pending_locked(self, gname, tid, value):
        """Record one trainer's dense contribution AND fold it into the
        running partial sum at arrival time — the round-time per-trainer
        summation loop becomes a dict pop in _run_round."""
        per = self._pending.setdefault(gname, {})
        if tid in per:
            # fenced replay re-delivering a slot it already filled:
            # overwrite (never accumulate) and rebuild this partial
            per[tid] = value
            self._refold_partial_locked(gname)
            return
        per[tid] = value
        cur = self._partial.get(gname)
        self._partial[gname] = value if cur is None else cur + value

    def _reset_stream_locked(self, tid):
        """Full per-trainer stream reset: round state PLUS the fold
        fences.  For any transition that starts a FRESH incarnation
        lineage for the id (eviction, admission, re-registration) — a
        stale fold fence would drop the new process's first rounds as
        replays, since its step tokens restart at 1."""
        self._clear_round_state_locked(tid)
        self._folded_send.pop(tid, None)
        self._folded_fetch.pop(tid, None)

    def _evict_locked(self, trainer_id, why):
        """Remove a dead trainer from the round (called under self._cv):
        drop its unsummed dense grads and queued sparse rows, then
        re-evaluate pending barriers against the surviving live set — the
        round must complete instead of hanging on a ghost."""
        tid = int(trainer_id)
        if tid not in self._live:
            return
        self._live.discard(tid)
        self._tracked.pop(tid, None)
        # a departed trainer's clock must not hold the staleness bound:
        # dropping it (and the notify below) releases parked peers
        self._trainer_clock.pop(tid, None)
        self._evicted.add(tid)
        self.counters["evictions"] += 1
        print("PSERVER EVICT trainer=%d round=%d: %s"
              % (tid, self._round, why), flush=True)
        self._reset_stream_locked(tid)
        # durable membership shrink: a new plan epoch is due (minted at
        # the next boundary — or right here when no round is in flight)
        self._mark_plan_dirty_locked()
        # a joiner parked in `register` is ALIVE: an eviction that
        # exposed a round boundary admits it (and an empty live set must
        # admit rather than declare the job done)
        self._admit_pending_joins_locked()
        if not self._live:
            self._done.set()
        elif self.sync_mode:
            self._reeval_barriers_locked()
        self._cv.notify_all()

    # ---- elastic autoscaling: plan epochs -------------------------------
    def _mark_plan_dirty_locked(self):
        """The live set changed durably: a new plan epoch is due.  The
        mint itself is deferred to the next round boundary (sync mode) —
        bumping mid-assembly would stale-fence the survivors' own
        in-flight frames and hang the round they are completing."""
        self._plan_dirty = True
        self._maybe_mint_plan_locked()

    def _maybe_mint_plan_locked(self):
        """Mint the pending plan epoch if we are at a boundary (async
        mode has no rounds, so dirty mints immediately).  Closes the
        current membership phase for the phase log."""
        if not self._plan_dirty:
            return
        if self.sync_mode and not self._at_boundary_locked():
            return
        if not self._live:
            # an empty world has nobody to plan for: stay dirty — if a
            # parked join readmits, its admission re-triggers the mint
            # with a real world; if the job is truly over, the flag
            # dies with the server
            return
        import time

        now = time.monotonic()
        self._phases.append({
            "epoch": self._phase["epoch"], "world": self._phase["world"],
            "rounds": self._round - self._phase["round0"],
            "wall_s": round(now - self._phase["t0"], 3)})
        self._plan_epoch += 1
        self._plan_dirty = False
        self.counters["plan_epochs"] += 1
        self._phase = {"epoch": self._plan_epoch,
                       "world": len(self._live),
                       "round0": self._round, "t0": now}
        print("PSERVER PLAN-EPOCH epoch=%d world=%d round=%d"
              % (self._plan_epoch, len(self._live), self._round),
              flush=True)
        self._cv.notify_all()

    def _phases_snapshot_locked(self):
        """Closed phases plus the still-open one — the per-membership
        steps/s evidence PSERVER-STATS and the bench elastic leg read."""
        import time

        return self._phases + [{
            "epoch": self._phase["epoch"], "world": self._phase["world"],
            "rounds": self._round - self._phase["round0"],
            "wall_s": round(time.monotonic() - self._phase["t0"], 3)}]

    def _stale_plan_locked(self, pepoch):
        """True when a frame carries a plan epoch older than the
        server's — the sender has not yet re-derived its plan for the
        current world.  Fenced exactly like a stale incarnation: the
        frame is dropped (counted) and the reply tells the sender which
        epoch to re-plan for; folding it would mix grad scales from two
        different worlds into one round (or resurrect a dead round's
        stream after a membership change)."""
        if pepoch is None or int(pepoch) >= self._plan_epoch:
            return False
        self.counters["stale_plan_drops"] += 1
        return True

    def _h_plan(self, trainer_id=0):
        """The re-plan handshake: the current plan epoch and world size.
        Trainers call this when a reply reveals a newer epoch, then
        re-derive their plan (transpiler.derive_plan) for the returned
        world."""
        with self._cv:
            return {"epoch": self._plan_epoch,
                    "world": max(1, len(self._live)),
                    "live": sorted(self._live),
                    "trainers": self.num_trainers,
                    # live pserver migration: the CURRENT pserver world
                    # — trainers re-derive block/shard dispatch over it
                    # (empty for pre-migration servers: the client then
                    # keeps its spec endpoints)
                    "endpoints": list(self._ps_world)}

    def _plan_reply_locked(self, reply):
        """Stamp the current plan epoch into a reply ONCE elasticity has
        engaged (epoch > 0): trainers note it passively off their normal
        traffic and re-plan at their next step.  Epoch-0 replies stay
        byte-identical to the pre-elastic wire."""
        if self._plan_epoch > 0:
            reply["pepoch"] = self._plan_epoch
        return reply

    # ---- live pserver shard migration (journaled handoff) ----------------
    # docs/FAULT_TOLERANCE.md "Live shard migration".  Two-phase, driven
    # by the supervisor (or an admin `migrate` client):
    #   migrate_begin(world) — wait for a round boundary, FREEZE state
    #     mutation, serialize every shard this server owns under the OLD
    #     dispatch but not the NEW one as crc-framed journal records, and
    #     ship them to their new owners (`migrate_in`), which apply them
    #     through the same live paths journal replay uses and fsync a
    #     snapshot BEFORE acking.  Any failure aborts: unfreeze, keep
    #     everything, old assignment stays authoritative.
    #   migrate_commit(world) — adopt the new pserver world, drop the
    #     moved state, unfreeze, and mint the plan epoch.  The supervisor
    #     only commits after EVERY server's begin acked, so the epoch
    #     provably never mints before target durability.
    # A timed-out freeze self-aborts (a dead supervisor must throttle the
    # cluster, never deadlock it); the later commit then reads stale and
    # the supervisor restarts the whole handoff, re-capturing fresh state
    # (migrate_in overwrites by name — idempotent).
    def _recalc_lr_trigger_locked(self):
        """The async lr-program trigger is keyed to ONE designated grad
        (min name) — migration adding or removing shards must re-derive
        it, or a server whose trigger shard moved away stops advancing
        its lr schedule (and the rowless slot-state catch-up keyed to
        it), and an elastic-grown server would never start."""
        self._lr_trigger = (min(self.grad_to_shard)
                            if self.grad_to_shard else None)

    def _freeze_wait_locked(self):
        """Park a state-mutating verb while a shard handoff is capturing
        /shipping.  Bounded like the staleness park: freeze throttles,
        never deadlocks."""
        if not self._frozen:
            return
        limit = max(10.0, 3.0 * self.eviction_deadline)
        self._cv.wait_for(
            lambda: not self._frozen or self._done.is_set(),
            timeout=limit)

    def _mig_frame(self, rec):
        """One journal-format frame: [8B len][4B crc32][pickle] — the
        exact on-disk record framing, reused as the handoff transport so
        the receiver validates and replays with the same discipline."""
        import pickle
        import zlib

        payload = pickle.dumps(rec, protocol=pickle.HIGHEST_PROTOCOL)
        return _J_HEAD.pack(len(payload),
                            zlib.crc32(payload) & 0xFFFFFFFF) + payload

    @staticmethod
    def _mig_unframe(frame):
        """Validate + decode one handoff frame; raises on length/crc
        mismatch (a torn frame must fail the handoff loudly, exactly as
        a torn journal record ends a replay — never apply garbage)."""
        import pickle
        import zlib

        if len(frame) < _J_HEAD.size:
            raise ValueError("migration frame shorter than its header")
        ln, crc = _J_HEAD.unpack_from(frame, 0)
        payload = frame[_J_HEAD.size:]
        if ln != len(payload) or ln > _J_MAX_RECORD:
            raise ValueError("migration frame length mismatch")
        if zlib.crc32(payload) & 0xFFFFFFFF != crc:
            raise ValueError("migration frame crc mismatch")
        return pickle.loads(payload)

    def _derive_ps_plan(self, endpoints):
        from ..transpiler.distribute_transpiler import derive_plan

        return derive_plan(self.plan_spec,
                           world={"endpoints": list(endpoints)})

    def _serialize_dense_shard_locked(self, gblock, idx):
        """One moving dense shard as a journal record: its optimizer
        shard program plus every per-block persistable var (param block,
        sliced moments, private beta pows — everything suffixed with
        this block's index).  Whole (shared) vars ship separately."""
        prog = self.shard_programs[self.grad_to_shard[gblock]]
        suffix = ".block%d" % int(idx)
        vars_out, whole = {}, {}
        for name, v in sorted(prog.global_block().vars.items()):
            if not getattr(v, "persistable", False):
                continue
            cur = self.scope.find_var(name)
            if cur is None:
                continue
            if name.endswith(suffix):
                vars_out[name] = np.array(cur)
            else:
                whole[name] = np.array(cur)
        return ({"k": "mshard", "g": gblock, "i": int(idx),
                 "prog": prog.to_json(), "vars": vars_out}, whole)

    def _serialize_sparse_shard_locked(self, shard):
        info = self.sparse_tables[shard]
        payload = {
            kk: (np.array(vv) if isinstance(vv, np.ndarray) else vv)
            for kk, vv in info.items()
            if kk in ("tbl", "lr", "opt")
            or kk.startswith(("moment", "beta", "velocity"))}
        fences = {str(t): int(sq)
                  for (t, tb), sq in self._sparse_fence.items()
                  if tb == shard}
        return {"k": "mtable", "t": str(shard),
                "s": int(self._sparse_shard_idx.get(shard, -1)),
                "info": payload, "fences": fences}

    def _serialize_sparse_tail_locked(self, shard):
        """Frozen FINAL TAIL of a delta handoff: only the rows dirtied
        since the unfrozen snapshot shipped, plus the non-row scalars
        (adam beta pows, lr) and the fold fences — the target overlays
        them on the snapshot it already holds, reconstructing the exact
        frozen state.  Falls back to the full record when row tracking
        went whole-table (momentum decay, shard bounce-back)."""
        d = (self._mig_dirty or {}).get(shard, None)
        if self._mig_dirty is None or shard not in self._mig_dirty \
                or d is None:
            return self._serialize_sparse_shard_locked(shard)
        info = self.sparse_tables[shard]
        ids = np.asarray(sorted(d), np.int64)
        rows = {}
        for kk, vv in info.items():
            if isinstance(vv, np.ndarray) and (
                    kk == "tbl"
                    or kk.startswith(("moment", "velocity"))):
                rows[kk] = np.array(vv[ids]) if ids.size else \
                    np.zeros((0,) + vv.shape[1:], vv.dtype)
        scal = {kk: vv for kk, vv in info.items()
                if kk == "lr" or (kk.startswith("beta")
                                  and not isinstance(vv, np.ndarray))}
        fences = {str(t): int(sq)
                  for (t, tb), sq in self._sparse_fence.items()
                  if tb == shard}
        return {"k": "mrows", "t": str(shard), "i": ids, "rows": rows,
                "scal": scal, "fences": fences}

    def _moving_sets_locked(self, new_world):
        """The shards THIS server owns under the old dispatch but not
        the new: [(gblock, new_ep, idx), ...], [(shard, new_ep), ...].
        Shared by the begin capture and the restart-recovery commit."""
        old_plan = self._derive_ps_plan(self._ps_world)
        new_plan = self._derive_ps_plan(new_world)
        grads = {str(p): str(g) for p, _s, _d, g in
                 self.plan_spec["params"]}
        dense, sparse = [], []
        for (p, idx), old_ep in sorted(old_plan["block_eps"].items()):
            if old_ep != self.endpoint:
                continue
            new_ep = new_plan["block_eps"][(p, idx)]
            if new_ep == self.endpoint:
                continue
            gblock = "%s.block%d" % (grads[p], idx)
            if gblock not in self.grad_to_shard:
                continue  # already handed off (idempotent retry)
            dense.append((gblock, new_ep, int(idx)))
        for shard, s in sorted(self._sparse_shard_idx.items()):
            if shard not in self.sparse_tables:
                continue
            old_ep = old_plan["sparse_eps"][s]
            new_ep = new_plan["sparse_eps"][s]
            if old_ep != self.endpoint or new_ep == self.endpoint:
                continue
            sparse.append((shard, new_ep))
        return dense, sparse

    def _shard_var_names_locked(self, gblock, idx):
        """Persistable per-block vars of one dense shard (the state that
        moves with it)."""
        prog = self.shard_programs[self.grad_to_shard[gblock]]
        suffix = ".block%d" % int(idx)
        return sorted(
            n for n, v in prog.global_block().vars.items()
            if getattr(v, "persistable", False) and n.endswith(suffix))

    def _mig_capture_locked(self, new_world, delta=False):
        """Compute the moving set (old dispatch vs new) and serialize it
        into per-target frame lists.  Called frozen, at a boundary.
        `delta`: the sparse tables' full snapshots already shipped
        unfrozen — serialize only their dirty-row tails (dense shards,
        whole vars and fences always ship here, in the freeze)."""
        dense, sparse = self._moving_sets_locked(new_world)
        targets = {}   # ep -> [frame, ...]
        whole_all = {}
        moved_dense, moved_sparse = [], []
        for gblock, new_ep, idx in dense:
            rec, whole = self._serialize_dense_shard_locked(gblock, idx)
            targets.setdefault(new_ep, []).append(self._mig_frame(rec))
            whole_all.update(whole)
            moved_dense.append((gblock, new_ep, sorted(rec["vars"])))
        for shard, new_ep in sparse:
            rec = (self._serialize_sparse_tail_locked(shard) if delta
                   else self._serialize_sparse_shard_locked(shard))
            targets.setdefault(new_ep, []).append(self._mig_frame(rec))
            moved_sparse.append((shard, new_ep))
        if targets:
            # shared state a FRESH target needs: whole vars (scheduled
            # lr values, step counters) + the lr program; applied
            # set-if-absent so an established server's own copies win
            if self.lr_program is not None:
                for name, v in sorted(
                        self.lr_program.global_block().vars.items()):
                    if getattr(v, "persistable", False):
                        cur = self.scope.find_var(name)
                        if cur is not None:
                            whole_all.setdefault(name, np.array(cur))
            wrec = self._mig_frame({
                "k": "mwhole", "vars": whole_all,
                "lr_program": (self.lr_program.to_json()
                               if self.lr_program is not None else None)})
            # the per-trainer FOLD FENCES travel with the state: the
            # captured shards already contain every round this server
            # folded, and a post-flip re-ship of the transition round
            # must drop as dup_round at the NEW owner exactly as it
            # would have here — a fresh target without the fences would
            # apply an already-contained round a second time (the
            # double-apply race the 2->3 chaos E2E caught)
            frec = self._mig_frame({
                "k": "mfence",
                "send": {str(t): int(s)
                         for t, s in self._folded_send.items()},
                "fetch": {str(t): int(s)
                          for t, s in self._folded_fetch.items()}})
            for ep in targets:
                targets[ep].append(wrec)
                targets[ep].append(frec)
        return targets, moved_dense, moved_sparse

    def _abort_mig_locked(self, why):
        if self._mig is None and not self._frozen:
            return
        self.counters["migrate_aborts"] += 1
        print("PSERVER MIGRATE-ABORT ep=%s: %s"
              % (self.endpoint, why), flush=True)
        self._mig = None
        self._mig_dirty = None
        self._mig_gen += 1
        self._frozen = False
        self._cv.notify_all()

    def _mig_timeout(self, gen):
        with self._cv:
            if self._frozen and self._mig_gen == gen:
                self._abort_mig_locked(
                    "freeze timed out waiting for commit — the "
                    "supervisor died mid-handoff; unfreezing (the old "
                    "assignment stays authoritative)")

    def _h_migrate_begin(self, world, trainer_id=0, delta=False):
        """Phase 1 of the handoff (see section comment).

        ``delta=True`` — incremental delta handoff: the bulky sparse
        tables ship as an UNFROZEN snapshot first, while this server
        keeps serving and tracks which rows mutate (_mig_dirty); the
        freeze then covers only the FINAL TAIL — dirty rows (mrows),
        dense shards, whole vars, fences.  ``freeze_ms`` in the reply
        is that frozen window: with a large embedding shard it shrinks
        from ~the whole handoff to the dirty fraction, which is the
        point."""
        import time

        if not self.plan_spec or not self.endpoint:
            return {"ok": False,
                    "error": "no re-derivable plan spec: this server "
                             "cannot compute shard dispatch for a new "
                             "world (custom dispatcher or legacy "
                             "per-variable wire) — migration refused"}
        world = [str(e) for e in world]
        t0 = time.monotonic()
        limit = max(10.0, 3.0 * self.eviction_deadline)
        pre_bytes = 0
        if delta:
            # ---- phase 1a: unfrozen sparse snapshot + dirty tracking
            with self._cv:
                if self._frozen or self._mig is not None:
                    return {"ok": False, "busy": True}
                try:
                    _dense, snap_sparse = self._moving_sets_locked(world)
                    pre_targets = {}
                    for shard, new_ep in snap_sparse:
                        rec = self._serialize_sparse_shard_locked(shard)
                        pre_targets.setdefault(new_ep, []).append(
                            self._mig_frame(rec))
                except Exception as e:
                    import traceback

                    traceback.print_exc()
                    return {"ok": False,
                            "error": "delta snapshot failed: %s" % e}
                # arm dirty tracking BEFORE the lock drops: every row
                # an application touches from here on rides the tail
                self._mig_dirty = {shard: set()
                                   for shard, _ in snap_sparse}
            pre_bytes = sum(len(f) for frames in pre_targets.values()
                            for f in frames)
            snap_err = None
            from .rpc import RPCClient

            for ep, frames in sorted(pre_targets.items()):
                try:
                    r = RPCClient.get(ep).call(
                        "migrate_in", timeout_s=600.0, frames=frames,
                        source=self.endpoint)
                    if not (isinstance(r, dict) and r.get("ok")):
                        snap_err = ("target %s refused the snapshot: %r"
                                    % (ep, r))
                        break
                except Exception as e:
                    snap_err = ("target %s failed mid-snapshot: %s"
                                % (ep, e))
                    break
            if snap_err is not None:
                with self._cv:
                    self._mig_dirty = None
                return {"ok": False, "error": snap_err}
        with self._cv:
            if self._frozen or self._mig is not None:
                self._mig_dirty = None
                return {"ok": False, "busy": True}
            if not self._cv.wait_for(
                    lambda: self._at_boundary_locked()
                    or self._done.is_set(), timeout=limit):
                self._mig_dirty = None
                return {"ok": False, "busy": True,
                        "error": "no round boundary within %.0fs" % limit}
            self._frozen = True
            f0 = time.monotonic()  # the freeze window starts HERE
            self._mig_gen += 1
            gen = self._mig_gen
            try:
                targets, moved_dense, moved_sparse = \
                    self._mig_capture_locked(world, delta=delta)
            except Exception as e:
                import traceback

                traceback.print_exc()
                self._abort_mig_locked("capture failed: %s" % e)
                return {"ok": False, "error": "capture failed: %s" % e}
            nbytes = pre_bytes + sum(len(f)
                                     for frames in targets.values()
                                     for f in frames)
            self._mig = {"world": world, "gen": gen,
                         "dense": moved_dense, "sparse": moved_sparse,
                         "bytes": nbytes}
            timer = threading.Timer(limit, self._mig_timeout, args=(gen,))
            timer.daemon = True
            timer.start()
        if targets:
            # chaos hook: SIGKILL the SOURCE mid-serialize (captured,
            # nothing shipped) — the old assignment must stay
            # authoritative and the retried handoff re-captures fresh
            self._maybe_migrate_crash("serialize")
        # ship OUTSIDE the lock: the freeze keeps captured state
        # consistent while frames are on the wire, and reads/heartbeats
        # keep flowing.  Any target failure aborts the whole handoff —
        # the epoch never mints for a partial transfer.
        shipped = {}
        err = None
        from .rpc import RPCClient

        for ep, frames in sorted(targets.items()):
            try:
                r = RPCClient.get(ep).call(
                    "migrate_in", timeout_s=600.0, frames=frames,
                    source=self.endpoint)
                if not (isinstance(r, dict) and r.get("ok")):
                    err = "target %s refused the handoff: %r" % (ep, r)
                    break
                shipped[ep] = int(r.get("applied", 0))
            except Exception as e:
                err = "target %s failed mid-handoff: %s" % (ep, e)
                break
        with self._cv:
            if err is not None:
                self._abort_mig_locked(err)
                return {"ok": False, "error": err}
            if self._mig is None or self._mig.get("gen") != gen:
                # the freeze self-aborted while we were shipping
                return {"ok": False, "stale": True,
                        "error": "freeze timed out during shipping"}
            moved = len(moved_dense) + len(moved_sparse)
            self.counters["migrated_shards_out"] += moved
            self.counters["migrated_bytes_out"] += nbytes
        freeze_ms = (time.monotonic() - f0) * 1e3
        print("PSERVER MIGRATE-BEGIN ep=%s world=%s moved=%d bytes=%d "
              "ms=%.1f freeze_ms=%.1f delta=%d"
              % (self.endpoint, world, moved, nbytes,
                 (time.monotonic() - t0) * 1e3, freeze_ms, int(delta)),
              flush=True)
        return {"ok": True, "moved": moved, "bytes": nbytes,
                "targets": shipped,
                "ms": round((time.monotonic() - t0) * 1e3, 3),
                "freeze_ms": round(freeze_ms, 3)}

    def _h_migrate_commit(self, world, trainer_id=0):
        """Phase 2: adopt the new pserver world, drop moved state, mint.
        Only called by the driver after EVERY live server's begin acked
        (i.e. every moving shard is durable at its target)."""
        world = [str(e) for e in world]
        with self._cv:
            if self._mig is not None and self._mig["world"] != world:
                return {"ok": False, "stale": True}
            if self._mig is None:
                # RESTART-RECOVERY commit: this server was killed (and
                # restored) between its begin-ack and here — the capture
                # died with the old incarnation, but the driver only
                # commits after EVERY begin acked, so every moving shard
                # is already durable at its target.  Recompute the diff
                # and adopt; dropping our (possibly one-restart-round
                # stale) copies is the correct direction — the target's
                # shipped copy is the newer one.  Without this, the
                # driver would have to abort-and-re-begin AFTER another
                # server already minted, and the re-shipped stale copy
                # would overwrite rounds trainers applied at the target
                # in between (a lost update).
                if not self.plan_spec or not self.endpoint:
                    return {"ok": False, "stale": True}
                if world == self._ps_world:
                    # already committed before the kill: idempotent ack
                    return {"ok": True, "epoch": self._plan_epoch,
                            "retiring": self.endpoint not in world}
                limit = max(10.0, 3.0 * self.eviction_deadline)
                self._cv.wait_for(
                    lambda: self._at_boundary_locked()
                    or self._done.is_set(), timeout=limit)
                try:
                    dense, sparse = self._moving_sets_locked(world)
                except Exception as e:
                    return {"ok": False, "stale": True,
                            "error": "recovery diff failed: %s" % e}
                self._mig = {
                    "world": world, "gen": self._mig_gen,
                    "dense": [(g, ep,
                               self._shard_var_names_locked(g, idx))
                              for g, ep, idx in dense],
                    "sparse": sparse}
                print("PSERVER MIGRATE-COMMIT-RECOVERY ep=%s world=%s"
                      % (self.endpoint, world), flush=True)
            for gblock, _ep, var_names in self._mig["dense"]:
                si = self.grad_to_shard.pop(gblock, None)
                if si is not None:
                    self.shard_programs[si] = None
                for n in var_names:
                    self.scope.erase(n)
                    # a fetch of a dropped var under the old layout must
                    # answer stale_plan (re-plan + re-pull), never a
                    # KeyError crash
                    self._dropped_vars.add(n)
                self._adopted["programs"].pop(gblock, None)
                self._adopted["dropped"].append(gblock)
            for shard, _ep in self._mig["sparse"]:
                self.sparse_tables.pop(shard, None)
                for key in [k for k in self._sparse_fence
                            if k[1] == shard]:
                    del self._sparse_fence[key]
                self._adopted["sparse"].pop(shard, None)
                self._adopted["dropped"].append(shard)
            moved = len(self._mig["dense"]) + len(self._mig["sparse"])
            self._fused = None
            self._fused_ready = False
            self._recalc_lr_trigger_locked()
            self._ps_world = world
            retiring = (self.endpoint is not None
                        and self.endpoint not in world)
            self._mig = None
            self._mig_dirty = None
            self._mig_gen += 1  # disarms the freeze-timeout timer
            self._frozen = False
            if moved:
                self.counters["migrations_out"] += 1
            # the pserver membership changed durably: mint NOW (the
            # freeze held the server at a round boundary) so the next
            # trainer frame learns the new world
            self._mark_plan_dirty_locked()
            data = self._snapshot() if self.checkpoint_dir else None
            epoch = self._plan_epoch
            self._cv.notify_all()
        if data is not None:
            # synchronous: the new world (and the dropped shards) are
            # durable before the commit acks — a restart cannot
            # resurrect moved-away shards into double ownership
            self._write_snapshot(data)
        print("PSERVER MIGRATE-COMMIT ep=%s world=%s epoch=%d%s"
              % (self.endpoint, world, epoch,
                 " RETIRING" if retiring else ""), flush=True)
        return {"ok": True, "epoch": epoch, "retiring": retiring}

    def _h_migrate_abort(self, trainer_id=0):
        with self._cv:
            self._abort_mig_locked("driver requested abort")
            return {"ok": True}

    def _maybe_migrate_crash(self, point):
        """Deterministic chaos hook: PADDLE_TPU_MIGRATE_CRASH names the
        kill point ('recv' = before any record applies, 'ack' = after
        apply + fsync, before the ack leaves); the marker file (crash
        once) lets a supervised respawn run clean."""
        import os
        import signal

        if os.environ.get("PADDLE_TPU_MIGRATE_CRASH") != point:
            return
        marker = os.environ.get("PADDLE_TPU_MIGRATE_CRASH_ONCE")
        if marker and os.path.exists(marker):
            return
        if marker:
            with open(marker, "w") as f:
                f.write(point)
        print("PSERVER MIGRATE-CRASH point=%s" % point, flush=True)
        os.kill(os.getpid(), signal.SIGKILL)

    def _h_migrate_in(self, frames, source=None, trainer_id=0):
        """Target side of the handoff: validate each crc-framed journal
        record, apply it through the SAME paths journal replay uses,
        append it to this server's own journal (async mode), and fsync a
        snapshot BEFORE acking — acked == durable, so the source's
        commit (and the epoch mint behind it) can rely on it."""
        self._maybe_migrate_crash("recv")
        with self._cv:
            n = 0
            for frame in frames:
                rec = self._mig_unframe(frame)
                self._apply_journal_record(rec)
                self._journal_append_locked(rec)
                n += 1
                self.counters["migrated_bytes_in"] += len(frame)
                if rec.get("k") in ("mshard", "mtable"):
                    self.counters["migrated_shards_in"] += 1
            if n:
                self.counters["migrations_in"] += 1
            data = self._snapshot() if self.checkpoint_dir else None
        if data is not None:
            self._write_snapshot(data)  # fsync'd BEFORE the ack
        self._maybe_migrate_crash("ack")
        print("PSERVER MIGRATE-IN ep=%s source=%s records=%d durable=%s"
              % (self.endpoint, source, n, bool(self.checkpoint_dir)),
              flush=True)
        return {"ok": True, "applied": n,
                "durable": bool(self.checkpoint_dir)}

    def _h_retire(self, trainer_id=0):
        """Clean shutdown of a drained, migrated-away server: after its
        commit (all shards handed off, epoch minted, trainers
        re-planned), the driver retires it — the serve loop concludes
        and PSERVER-STATS prints, instead of an opaque SIGKILL."""
        with self._cv:
            print("PSERVER RETIRE ep=%s round=%d"
                  % (self.endpoint, self._round), flush=True)
            self._done.set()
            self._cv.notify_all()
            return {"ok": True}

    # ---- elastic rejoin --------------------------------------------------
    def _admit_locked(self, tid):
        """Admit a (re)joining trainer into the live set.  ONLY called at
        a round boundary: the barrier denominator must never grow while a
        round is being assembled, or survivors would wait on a joiner
        that was never part of the round."""
        was_evicted = tid in self._evicted
        grew = tid not in self._live
        self._live.add(tid)
        self._evicted.discard(tid)
        self._completed.discard(tid)
        self._reset_stream_locked(tid)
        self._done.clear()
        if was_evicted:
            self.counters["readmissions"] += 1
            print("PSERVER READMIT trainer=%d round=%d" % (tid, self._round),
                  flush=True)
        if grew:
            # admission only happens at a boundary, so the epoch mints
            # immediately: the joiner's very first `plan` fetch (and the
            # survivors' next-round re-plan) see the grown world
            self._mark_plan_dirty_locked()

    def _admit_pending_joins_locked(self):
        """Admit parked joins IF the server is at a round boundary —
        self-guarded, so it is safe (and necessary) to call from every
        state transition that can CREATE a boundary: _run_round, the
        fetch-barrier clears, eviction and completion."""
        if not self._pending_joins or not self._at_boundary_locked():
            return
        for tid in sorted(self._pending_joins):
            self._admit_locked(tid)
        self._pending_joins.clear()
        self._cv.notify_all()

    def _mid_round_locked(self):
        """True while the current round is being ASSEMBLED (some trainer
        has contributed grads or entered a barrier): admission now would
        change the barrier denominator under the survivors."""
        return bool(
            self._send_barriers or any(self._pending.values())
            or self._pending_sparse or self._send_seen
            or any(self._send_bucket_counts.values()))

    def _at_boundary_locked(self):
        """The round boundary: no round being assembled AND no fetch of
        the previously-served round still draining.  Admission while
        _fetch_barriers pends would grow the fetch denominator under the
        survivors — the stale entries could later complete with the
        joiner's first fetch and flip params_ready off while survivors
        still hold un-served gets (the _h_complete hazard, but
        re-introduced by growth instead of shrinkage)."""
        return not (self._mid_round_locked() or self._fetch_barriers
                    or self._fetch_seen
                    or any(self._fetch_bucket_counts.values()))

    def _h_register(self, trainer_id=0):
        """Trainer handshake + elastic (re)join.  A fresh trainer process
        declares itself: its per-step fold fences reset (its stream
        restarts at step 1), and if the id was evicted or completed it is
        readmitted — at a ROUND BOUNDARY only, blocking until the
        in-flight round completes so barrier totals stay consistent for
        both the joiner and the survivors (a fence, not a delay)."""
        import time

        with self._cv:
            tid = int(trainer_id)
            self.counters["registrations"] += 1
            if tid in self._live:
                # fast relaunch reusing a live id (died and came back
                # before eviction noticed): drop the old incarnation's
                # partial round state and stale fold fences
                self._reset_stream_locked(tid)
            elif not self.sync_mode or self._at_boundary_locked():
                self._admit_locked(tid)
            else:
                self._pending_joins.add(tid)
                self._cv.wait_for(
                    lambda: tid in self._live or self._done.is_set())
                self._pending_joins.discard(tid)
                if tid not in self._live:
                    return {"ok": False, "done": True,
                            "round": self._round}
            if tid in self._tracked:
                self._tracked[tid] = time.monotonic()
            self._cv.notify_all()
            return self._plan_reply_locked(
                {"ok": True, "live": True, "round": self._round,
                 "world": max(1, len(self._live)),
                 "incarnation": self.incarnation})

    def _h_stats(self, trainer_id=0):
        """Recovery observability: incarnation, round, live/evicted sets,
        the eviction/readmission counters, and — async mode — the
        per-trainer logical clocks, staleness bound, async send count and
        journal/park evidence (rpc.get_comm_stats's server-side
        sibling)."""
        with self._cv:
            # load-aware scaling signals (docs/FAULT_TOLERANCE.md "Live
            # shard migration"): server-side pending work the
            # supervisor's _ScalingPolicy polls live — queue depth is
            # the number of un-applied per-trainer contributions +
            # queued sparse chunks, pending_bytes their payload (the
            # server-side bytes-in-flight)
            qd = (sum(len(per) for per in self._pending.values())
                  + len(self._pending_sparse))
            pb = (sum(int(v.nbytes) for per in self._pending.values()
                      for v in per.values())
                  + sum(int(np.asarray(c[1]).nbytes)
                        for c in self._pending_sparse.values()))
            out = {"round": self._round, "incarnation": self.incarnation,
                   "live": sorted(self._live),
                   "evicted": sorted(self._evicted),
                   "async_sends": self._async_sends,
                   "staleness_bound": self._staleness_bound,
                   # elastic autoscaling evidence: the current epoch +
                   # the per-membership-phase round log
                   "plan_epoch": self._plan_epoch,
                   "world": len(self._live),
                   "phases": self._phases_snapshot_locked(),
                   "queue_depth": qd,
                   "pending_bytes": pb,
                   "ps_world": list(self._ps_world),
                   # runtime surface for the reduced legacy guarantee
                   # (journaled-but-unfenced per-var async path)
                   "unfenced_async": bool(self._unfenced_async),
                   # rpc dict keys must be strings (closed wire types)
                   "clocks": {str(t): c
                              for t, c in sorted(
                                  self._trainer_clock.items())}}
            out.update(self.counters)
            return out

    def _complete_fetch_barrier_locked(self):
        """Every live trainer folded its fetch: reset the serve epoch.
        The single home for the clear/flip/admit sequence — the fenced
        fold, the legacy fold, the explicit barrier verb and eviction
        re-evaluation all converge here."""
        self._fetch_barriers.clear()
        self._params_ready = False
        # fetch drained: a round boundary — parked joins admit, pending
        # plan epochs mint
        self._admit_pending_joins_locked()
        self._maybe_mint_plan_locked()
        self._cv.notify_all()

    def _reeval_barriers_locked(self):
        """The live set shrank (eviction / clean departure): pending
        barriers re-evaluate against the survivors.  FETCH first — a
        pending fetch barrier belongs to the round already SERVED, and
        re-evaluating it after _run_round would flip the fresh round's
        params_ready back off, hanging every surviving get on a flag
        nothing will set again."""
        if (self._fetch_barriers
                and len(self._fetch_barriers) >= len(self._live)):
            self._complete_fetch_barrier_locked()
        if (self._send_barriers
                and len(self._send_barriers) >= len(self._live)):
            self._run_round()
        else:
            # the shrink itself may have exposed a round boundary
            self._admit_pending_joins_locked()

    # ---- verb dispatch ---------------------------------------------------
    def handle(self, verb, **kw):
        tid = kw.get("trainer_id")
        if isinstance(tid, int) and tid in self._tracked:
            # lock-free liveness stamp at RECEIVE time (dict assignment
            # is GIL-atomic): a handler queued behind the round lock
            # while _run_round executes a long optimize step must not go
            # stale waiting — the reaper would mass-evict healthy
            # trainers the instant the round releases the lock
            import time

            self._tracked[int(tid)] = time.monotonic()
        try:
            return getattr(self, "_h_" + verb)(**kw)
        except Exception as e:  # ship errors to the client
            import traceback

            return {"__error__": "%s\n%s" % (e, traceback.format_exc())}

    # ---- optimize --------------------------------------------------------
    def _apply_shard(self, shard_idx, feed):
        prog = self.shard_programs[shard_idx]
        self.exe.run(prog, feed=feed, fetch_list=[], scope=self.scope)

    def _ensure_fused_locked(self):
        """Build the fused-apply plan on the first round (lazy: stub
        shard programs in unit tests must not crash the constructor).
        Any analysis surprise degrades to the per-block path, loudly."""
        if self._fused_ready:
            return self._fused
        self._fused_ready = True
        from ..flags import get_flag

        if not get_flag("ps_fused_apply"):
            return None
        try:
            from .fused_apply import FusedApply

            fused = FusedApply(self.shard_programs, self.grad_to_shard,
                               self.scope)
            if fused.specs:
                self._fused = fused
        except Exception:
            import traceback

            traceback.print_exc()
        return self._fused

    def _run_round(self):
        """All send-barriers in: run lr, apply the (arrival-time-folded)
        grad sums — one jitted fused call per optimizer group, per-block
        executor programs for anything unfusable — then the queued
        sparse updates (after lr, so a scheduled lr is this round's
        decayed value — the order the local program runs in)."""
        from ..profiler import RecordEvent

        if self.lr_program is not None:
            self.exe.run(self.lr_program, feed={}, fetch_list=[], scope=self.scope)
        totals = {}
        for gname, per_trainer in sorted(self._pending.items()):
            total = self._partial.get(gname)
            if total is None:  # defensive: fold record missing
                for v in per_trainer.values():
                    total = v if total is None else total + v
            totals[gname] = total
        fused = self._ensure_fused_locked()
        with RecordEvent("ps_apply_round", cat="apply"):
            if fused is not None:
                totals = fused.apply(totals)
            for gname in sorted(totals):
                self._apply_shard(self.grad_to_shard[gname],
                                  {gname: totals[gname]})
        self._partial.clear()
        by_table = {}
        for (tid, t) in sorted(self._pending_sparse):
            by_table.setdefault(t, []).append(self._pending_sparse[(tid, t)])
        for t, chunks in sorted(by_table.items()):
            self._apply_sparse(
                t,
                np.concatenate([c[0] for c in chunks]),
                np.concatenate([c[1] for c in chunks], axis=0),
                advance_pows=False,
            )
        self._pending_sparse = {}
        # per-round state that advances even on ROWLESS rounds: the
        # local op runs every step regardless of which rows a shard's id
        # hashing happened to receive — adam beta pows advance
        # (ops/optimizer_ops.py Beta1PowOut) and momentum velocity
        # decays (the densified SparseMomentumFunctor covers every row)
        for t, info in sorted(self.sparse_tables.items()):
            self._advance_pows(info)
            if t not in by_table and (
                    (info.get("opt") or {}).get("type") == "momentum"):
                self._apply_sparse(t, np.zeros((0,), np.int64),
                                   np.zeros((0, info["tbl"].shape[1]),
                                            info["tbl"].dtype),
                                   advance_pows=False)
        self._pending.clear()
        self._send_barriers.clear()
        # fetch-barrier stragglers from the PREVIOUS serve epoch (a
        # fenced replay's re-fold of a round its peers already finished
        # fetching — no survivor will ever complete that barrier) must
        # not carry into the new round: a leftover entry would let the
        # next round's fetch barrier complete one fold early, flipping
        # params_ready off under a trainer's still-unserved get
        self._fetch_barriers.clear()
        self._params_ready = True
        self._round += 1
        self._maybe_checkpoint()
        # round boundary: admit trainers parked in `register` — the NEXT
        # round's barrier totals include them from its very first bucket
        self._admit_pending_joins_locked()
        # ... and mint any pending plan epoch: a membership change that
        # landed mid-round becomes visible to trainers exactly one round
        # after it happened (their blocking send replies carry it)
        self._maybe_mint_plan_locked()
        self._cv.notify_all()

    # ---- handlers --------------------------------------------------------
    def _apply_async_send_locked(self, name, value):
        """One async dense grad application, lr-trigger bookkeeping
        included — the shared core of the live verbs AND journal replay,
        so a replayed stream advances the lr schedule and the sparse
        slot-state catch-up identically to the original arrivals."""
        if name == self._lr_trigger:
            if self.lr_program is not None:
                self.exe.run(
                    self.lr_program, feed={}, fetch_list=[],
                    scope=self.scope
                )
            # per-step catch-up for sparse tables that saw NO rows
            # since the last trigger: their adam beta-pows advance
            # and momentum velocity decays exactly as a sync
            # rowless round would (ADVICE r5; module docstring
            # documents the residual approximation)
            for t, info in sorted(self.sparse_tables.items()):
                if t in self._async_touched:
                    continue
                typ = (info.get("opt") or {}).get("type")
                if typ == "adam":
                    self._advance_pows(info)
                elif typ == "momentum":
                    self._apply_sparse(
                        t, np.zeros((0,), np.int64),
                        np.zeros((0, info["tbl"].shape[1]),
                                 info["tbl"].dtype),
                        advance_pows=False)
            self._async_touched.clear()
        self._apply_shard(self.grad_to_shard[name], {name: value})
        self._async_sends += 1

    def _async_dense_ckpt_locked(self):
        """Checkpoint cadence for async dense traffic, checked ONLY
        after the triggering bucket's journal record + fence commit are
        down.  Firing mid-bucket (the old per-send modulo inside the
        apply) captured a snapshot containing the bucket's effects and
        rotated the journal BEFORE that bucket's record was appended —
        the record then sat past the rotation point and a restore
        replayed it onto state that already contained it (double
        apply)."""
        if self._replaying or not self.checkpoint_dir:
            return
        cadence = self.checkpoint_every * max(1, len(self.grad_to_shard))
        if self._async_sends - self._sends_at_ckpt >= cadence:
            self._sends_at_ckpt = self._async_sends
            self._round += 1
            self._maybe_checkpoint()

    def _stale_shard_locked(self, names):
        """True when a frame names a grad shard this server no longer
        (or does not yet) own — the sender's dispatch predates a
        committed migration.  Replied like a stale plan: dropped, the
        sender re-plans and re-ships to the current owner."""
        if self.plan_spec is None:
            return False
        if any(n not in self.grad_to_shard for n in names):
            self.counters["stale_plan_drops"] += 1
            return True
        return False

    def _h_send(self, name, value, trainer_id=0):
        value = np.asarray(value)
        if not self.sync_mode:
            with self._cv:
                self._touch(trainer_id)
                self._freeze_wait_locked()
                if self._stale_shard_locked([name]):
                    return self._plan_reply_locked(
                        {"ok": True, "stale_plan": True,
                         "pepoch": self._plan_epoch})
                self._apply_async_send_locked(name, value)
                # legacy per-var path: journaled (a restart replays it)
                # but UNFENCED — only the bucketed path carries aseq
                # tokens, so exactly-once across SIGKILL needs buckets.
                # Surface the reduced guarantee at RUNTIME, loudly, the
                # first time it actually runs journaled (it used to be
                # documented only)
                if self._journal_enabled() and not self._unfenced_async:
                    self._unfenced_async = True
                    import sys

                    sys.stderr.write(
                        "PSERVER WARNING: legacy per-variable async "
                        "path (comm_bucket_bytes=0) is running "
                        "JOURNALED BUT UNFENCED — applied updates "
                        "survive SIGKILL, but an RPC retry straddling "
                        "a restart can double-apply (no aseq dedup).  "
                        "Use the bucketed wire "
                        "(FLAGS_comm_bucket_bytes>0) for exactly-once "
                        "(docs/FAULT_TOLERANCE.md)\n")
                self._journal_append_locked(
                    {"k": "v", "n": name, "v": value,
                     "tid": int(trainer_id)})
                self._async_dense_ckpt_locked()
            return {"ok": True}
        with self._lock:
            self._touch(trainer_id)
            if int(trainer_id) in self._evicted:
                # a ghost's late grads must not leak into a future round
                return {"ok": False, "evicted": True}
            self._fold_pending_locked(name, int(trainer_id), value)
        return {"ok": True}

    def _h_send_bucket(self, blocks, trainer_id=0, seq_total=None,
                       step=None, seq_idx=None, sparse_tables=None,
                       aseq=None, pepoch=None):
        """Coalesced grad frame: `blocks` maps grad block name -> value,
        shipped as ONE rpc round trip (see ops/dist_ops.py send_bucket).
        Server-side the bucket is unpacked into exactly the per-block
        paths _h_send uses — pending tables in sync mode, immediate shard
        application (with the lr-trigger bookkeeping) in async — so
        optimizer slot logic never sees the difference.

        `seq_total` (sync mode) folds the send barrier into the bucket
        stream: the trainer declares how many buckets it ships to THIS
        server per step, and the arrival of the last one (arrival ORDER
        is free — the window delivers out of order) counts as the
        trainer's send barrier, saving a dedicated blocking round trip.
        That last call blocks until the round runs, exactly like the
        explicit barrier verb it replaces.

        `step`/`seq_idx` (incarnation fencing) make the stream
        replay-safe: buckets are counted by (step, seq_idx) SET, so a
        trainer that re-ships its whole round after observing a pserver
        restart cannot advance the fold twice (pending slots are keyed —
        overwrite, not accumulate), and a replay of a step this server
        already FOLDED (it survived in the restored snapshot) is dropped
        at the `_folded_send` fence instead of double-applying a round."""
        if not self.sync_mode:
            # sorted order keeps the lr trigger (min grad name) firing
            # before the other shards of the same logical step WITHIN a
            # bucket.  Across buckets, comm_inflight > 1 can reorder
            # arrivals, so a multi-bucket async step may interleave the
            # trigger with another bucket's grads — one more term of the
            # documented async approximation (module docstring); sync
            # mode is exact, its ordering comes from the round barrier.
            with self._cv:
                self._touch(trainer_id)
                self._freeze_wait_locked()
                tid = int(trainer_id)
                if tid in self._evicted:
                    return {"ok": False, "evicted": True}
                if self._stale_shard_locked(blocks):
                    # migrated-away shard under a pre-flip dispatch: the
                    # async sender must re-plan and re-ship to the new
                    # owner (dropped here, never applied — and never
                    # journaled, so replay can't resurrect it either).
                    # dropped_aseq echoes the victim so the trainer's
                    # dense resend queue re-ships EXACTLY the dropped
                    # buckets (an applied-but-unacked one must not be
                    # re-shipped under a fresh aseq — that would bypass
                    # the dedup fence and double-apply)
                    return self._plan_reply_locked(
                        {"ok": True, "stale_plan": True,
                         "dropped_aseq": aseq,
                         "pepoch": self._plan_epoch})
                if aseq is not None and self._dense_fence_is_dup(tid, aseq):
                    # at-least-once re-delivery (RPC retry straddling a
                    # restart, or an incarnation-bump re-ship) of a bucket
                    # whose apply is already durable: drop, never double
                    self.counters["dedup_drops"] += 1
                    # dense_acked names the DENSE fence explicitly: the
                    # trainer drains this reply from a pipelined window
                    # mixed with other verbs' acks, and its dense resend
                    # queue must only prune on dense high-water
                    return self._plan_reply_locked(
                        {"ok": True, "dup": True,
                         "acked": self._dense_fence[tid][0],
                         "dense_acked": self._dense_fence[tid][0]})
                # NOTE: aseq never feeds _trainer_clock — it counts
                # BUCKETS per endpoint, not steps, so a multi-bucket
                # model would inflate a laggard's clock by the bucket
                # count and silently defeat the staleness bound.  The
                # clock is the sparse seq token alone (minted once per
                # STEP and shipped to every server, empties included).
                vals = {n: np.asarray(v) for n, v in blocks.items()}
                for name in sorted(vals):
                    self._apply_async_send_locked(name, vals[name])
                if aseq is not None:
                    # journal + fsync BEFORE the reply: an acked bucket is
                    # durable, an unacked one is re-shipped — exactly-once
                    # either way (the fence drops the dup)
                    self._journal_append_locked(
                        {"k": "d", "b": vals, "tid": tid, "q": aseq})
                    self._dense_fence_commit(tid, aseq)
                    self._async_dense_ckpt_locked()
                    return self._plan_reply_locked(
                        {"ok": True, "acked": self._dense_fence[tid][0],
                         "dense_acked": self._dense_fence[tid][0]})
                self._journal_append_locked(
                    {"k": "d", "b": vals, "tid": tid, "q": None})
                self._async_dense_ckpt_locked()
                return self._plan_reply_locked({"ok": True})
            return {"ok": True}
        with self._cv:
            self._touch(trainer_id)
            self._freeze_wait_locked()
            tid = int(trainer_id)
            if tid in self._evicted:
                return {"ok": False, "evicted": True}
            if self._stale_plan_locked(pepoch) \
                    or self._stale_shard_locked(blocks):
                # plan-epoch fence (elastic autoscaling): the sender's
                # world is out of date — its grads carry the OLD scale,
                # or name shards a committed migration moved away.
                # Dropped, never folded; the sender re-plans off the
                # reply and re-ships the round at the current epoch.
                return {"ok": True, "stale_plan": True,
                        "pepoch": self._plan_epoch}
            if seq_total and step is not None:
                step = int(step)
                if step <= self._folded_send.get(tid, -1):
                    # fenced replay of a round the restored state already
                    # contains: the fold record rode the same snapshot as
                    # the params, so applying again would double the round
                    self.counters["dup_round_drops"] += 1
                    return self._plan_reply_locked(
                        {"ok": True, "dup_round": True})
                prev = self._folded_send.get(tid)
                if prev is not None and step > prev + 1:
                    # the trainer replays only its CURRENT round, so any
                    # round between the restored snapshot and the stream
                    # is unrecoverable.  A gap of exactly ONE round is
                    # the unavoidable async-write race (the kill landed
                    # after _run_round but before its background
                    # snapshot hit disk): tolerate it LOUDLY — counted
                    # and printed, never silent.  A wider gap means the
                    # configuration itself discards rounds on every
                    # restore (checkpoint_every > 1, or snapshots
                    # repeatedly failing to land) — fail the job rather
                    # than quietly train past several lost updates.
                    lost = step - prev - 1
                    if lost > 1:
                        raise RuntimeError(
                            "incarnation fence gap: trainer %d is at "
                            "step %d but this server last folded step %d "
                            "— the restored checkpoint is missing %d "
                            "intermediate rounds that cannot be replayed "
                            "(trainers only record the current round); "
                            "refusing to silently drop them.  Lower "
                            "checkpoint_every so restores stay within "
                            "one round of the stream." % (tid, step, prev,
                                                          lost))
                    if self._send_step.get(tid) != step:
                        # count once per lost round, not once per
                        # arriving bucket of the gapped step (the reset
                        # below stamps _send_step before bucket 2)
                        self.counters["lost_rounds"] += 1
                        print("PSERVER LOST-ROUND trainer=%d step=%d "
                              "folded=%d: the kill raced the background "
                              "checkpoint write; one round's update is "
                              "lost" % (tid, step, prev), flush=True)
                if self._send_step.get(tid) != step:
                    self._send_step[tid] = step
                    self._send_seen[tid] = set()
            for name, value in blocks.items():
                self._fold_pending_locked(name, tid, np.asarray(value))
            if not seq_total:
                return self._plan_reply_locked({"ok": True})
            if step is not None:
                seen = self._send_seen[tid]
                seen.add(int(seq_idx or 0))
                if len(seen) < int(seq_total):
                    return self._plan_reply_locked({"ok": True})
                if sparse_tables:
                    # the trainer declared sparse chunks for this step:
                    # every one must be PENDING before the fold may run
                    # the round.  A crash between the sparse acks and
                    # the dense folds re-delivers only the (unacked)
                    # dense buckets via RPC retries — folding then would
                    # run the round without its sparse rows and the
                    # fence would drop the corrective replay as
                    # dup_round.  Refuse (keeping the assembled set);
                    # the fenced replay re-queues sparse first, and its
                    # re-shipped dense buckets re-trigger this check.
                    unknown = [t for t in sparse_tables
                               if t not in self.sparse_tables]
                    if unknown:
                        raise KeyError(
                            "send_bucket declares sparse tables this "
                            "server does not shard: %s" % unknown)
                    missing = [t for t in sparse_tables
                               if (tid, t) not in self._pending_sparse]
                    if missing:
                        return self._plan_reply_locked(
                            {"ok": True, "need_sparse": missing})
                self._folded_send[tid] = step
                self._send_step.pop(tid, None)
                self._send_seen.pop(tid, None)
            else:  # legacy count-based fold (pre-fencing callers)
                c = self._send_bucket_counts.get(tid, 0) + 1
                if c < int(seq_total):
                    self._send_bucket_counts[tid] = c
                    return {"ok": True}
                self._send_bucket_counts[tid] = 0
            # last bucket of this trainer's step: its send barrier
            self._send_barriers.add(trainer_id)
            if len(self._send_barriers) >= len(self._live):
                self._run_round()
            else:
                rnd = self._round
                self._cv.wait_for(
                    lambda: self._round > rnd or self._done.is_set()
                    or tid in self._evicted
                )
                if tid in self._evicted:
                    return {"ok": False, "evicted": True}
            # the blocking (folded-barrier) reply is constructed AFTER
            # the round ran — a boundary-minted epoch rides it, so every
            # survivor learns the new world exactly one round after the
            # membership change
            return self._plan_reply_locked({"ok": True})
        return {"ok": True}

    def _h_get_bucket(self, names, trainer_id=0, fetch_total=None,
                      step=None, seq_idx=None, wire_dtype=None):
        """Coalesced param fetch: one frame returns every requested block
        — and in sync mode ONE params-ready wait covers the whole bucket
        instead of one blocking round trip per variable.  `fetch_total`
        folds the fetch barrier in: when this trainer's last declared
        bucket has been served (any arrival order) it counts as the
        trainer's fetch barrier, and the round resets once every live
        trainer got theirs.  `step`/`seq_idx` mirror _h_send_bucket's
        fencing: a replayed fetch stream counts by set (never double-
        folds), and a fetch step this server already folded is served
        (reads are harmless) without counting.  `wire_dtype` (the
        REQUESTER's declaration, stamped into its bucket plan by the
        transpiler) compresses float blocks in the reply —
        'bfloat16' halves every param frame; the client decodes back
        to the original dtype (rpc.Bf16Wire).

        A fetch naming a MIGRATED-AWAY block (the sender's layout
        predates a committed handoff) answers stale_plan — the client
        re-plans and re-pulls from the new owner — instead of a
        KeyError crash.  Checked BEFORE the params wait: a stale fetch
        must return now, not park on a round that will never serve
        it."""
        if self.plan_spec is not None:
            gone = [n for n in names if n in self._dropped_vars]
            if gone:
                with self._cv:
                    self.counters["stale_plan_drops"] += 1
                    return self._plan_reply_locked(
                        {"stale_plan": True,
                         "pepoch": self._plan_epoch})
        if self.sync_mode:
            with self._cv:
                self._touch(trainer_id)
                # a REPLAYED fetch of a step this trainer already folded
                # (restart recovery) is served from the current params
                # without waiting: its own fold may have flipped
                # params_ready off, and parking here would deadlock the
                # replay on a flag only the next round sets
                already_folded = (
                    step is not None
                    and int(step) <= self._folded_fetch.get(
                        int(trainer_id), -1))
                if not already_folded:
                    self._cv.wait_for(
                        lambda: self._params_ready or self._done.is_set()
                    )
                if int(trainer_id) in self._evicted:
                    raise RuntimeError(
                        "trainer %s was evicted from the sync round; "
                        "params reflect a round it did not participate "
                        "in — restart the trainer to rejoin"
                        % (trainer_id,))
        out = {}
        for name in names:
            var = self.scope.find_var(name)
            if var is None:
                raise KeyError("pserver has no var %s" % name)
            out[name] = np.asarray(var)
        if wire_dtype not in (None, "float32", "bfloat16"):
            raise ValueError(
                "get_bucket: unknown wire_dtype %r" % (wire_dtype,))
        if wire_dtype == "bfloat16":
            from .rpc import Bf16Wire

            out = {n: (Bf16Wire(v) if v.dtype.kind == "f" else v)
                   for n, v in out.items()}
        if self.sync_mode and fetch_total:
            with self._cv:
                tid = int(trainer_id)
                if tid in self._evicted:
                    # evicted between the params wait and here: a ghost
                    # must not count toward the survivors' fetch barrier
                    raise RuntimeError(
                        "trainer %s was evicted from the sync round"
                        % (trainer_id,))
                if step is not None:
                    step = int(step)
                    if step <= self._folded_fetch.get(tid, -1):
                        return out  # replay of a folded fetch: serve only
                    if self._fetch_step.get(tid) != step:
                        self._fetch_step[tid] = step
                        self._fetch_seen[tid] = set()
                    seen = self._fetch_seen[tid]
                    seen.add(int(seq_idx or 0))
                    if len(seen) < int(fetch_total):
                        return out
                    self._folded_fetch[tid] = step
                    self._fetch_step.pop(tid, None)
                    self._fetch_seen.pop(tid, None)
                else:  # legacy count-based fold
                    c = self._fetch_bucket_counts.get(tid, 0) + 1
                    if c < int(fetch_total):
                        self._fetch_bucket_counts[tid] = c
                        return out
                    self._fetch_bucket_counts[tid] = 0
                self._fetch_barriers.add(trainer_id)
                if len(self._fetch_barriers) >= len(self._live):
                    self._complete_fetch_barrier_locked()
        return out

    def _h_barrier(self, kind, trainer_id=0):
        if not self.sync_mode:
            return {"ok": True}
        with self._cv:
            self._touch(trainer_id)
            if int(trainer_id) in self._evicted:
                return {"ok": False, "evicted": True}
            if kind == "send":
                self._send_barriers.add(trainer_id)
                if len(self._send_barriers) >= len(self._live):
                    self._run_round()
                else:
                    rnd = self._round
                    tid = int(trainer_id)
                    self._cv.wait_for(
                        lambda: self._round > rnd or self._done.is_set()
                        or tid in self._evicted
                    )
                    if tid in self._evicted:
                        # evicted WHILE blocked here (round moved on, or
                        # will, without our grads): report it now, not
                        # one stale step later
                        return {"ok": False, "evicted": True}
            elif kind == "fetch":
                self._fetch_barriers.add(trainer_id)
                if len(self._fetch_barriers) >= len(self._live):
                    self._complete_fetch_barrier_locked()
        return {"ok": True}

    def _h_get(self, name, trainer_id=0):
        if self.sync_mode:
            with self._cv:
                self._touch(trainer_id)
                self._cv.wait_for(
                    lambda: self._params_ready or self._done.is_set()
                )
                if int(trainer_id) in self._evicted:
                    raise RuntimeError(
                        "trainer %s was evicted from the sync round; "
                        "params reflect a round it did not participate "
                        "in — restart the trainer to rejoin"
                        % (trainer_id,))
        var = self.scope.find_var(name)
        if var is None:
            raise KeyError("pserver has no var %s" % name)
        return np.asarray(var)

    # ---- sparse embedding shards (distributed lookup table) -------------
    def _h_prefetch(self, table, ids, trainer_id=0, clock=None):
        """Serve embedding rows by local row id (prefetch_op analog).
        `clock` (async fenced mode) is the requesting trainer's logical
        clock: a lookup from a trainer past the staleness bound parks
        here — the read side of the bound, so a fast trainer cannot even
        OBSERVE rows more than `bound` steps ahead of the laggard.

        A migrated-away shard answers a stale_plan DICT instead of rows
        (never a KeyError crash): the client re-plans and re-reads from
        the shard's new owner."""
        if self.plan_spec is not None and table not in self.sparse_tables:
            with self._cv:
                self.counters["stale_plan_drops"] += 1
                return self._plan_reply_locked(
                    {"stale_plan": True, "pepoch": self._plan_epoch})
        tbl = self.sparse_tables[table]["tbl"]
        ids = np.asarray(ids).reshape(-1)
        ids = np.clip(ids, 0, tbl.shape[0] - 1)
        with self._cv:
            if clock is not None and not self.sync_mode:
                tid = int(trainer_id)
                self._touch(tid)
                self._clock_update_locked(tid, clock)
                self._park_if_stale_locked(tid, clock)
            return tbl[ids].copy()

    def _sparse_lr_value(self, info):
        """Current learning rate for a sparse table: the scheduled lr var
        from the pserver scope (decayed by lr_program) when named, else
        the captured constant, else the server-wide fallback.  A
        SCHEDULED lr (named var, no constant) whose var is missing is an
        error — silently training at a stale constant is the failure the
        old NotImplementedError guard existed to prevent."""
        opt = info.get("opt") or {}
        name = opt.get("lr_name")
        if name:
            var = self.scope.find_var(name)
            if var is not None:
                return (float(np.asarray(var).reshape(-1)[0])
                        * float(opt.get("lr_scale", 1.0)))
            if info.get("lr") is None:
                raise RuntimeError(
                    "sparse table optimizer needs scheduled lr var %r but "
                    "the pserver scope does not hold it (lr_program split "
                    "miss?) and no constant fallback was captured" % name)
        if info.get("lr") is not None:
            return float(info["lr"])
        return float(self.sparse_lr)

    def _advance_pows(self, info):
        """Advance an adam table's beta pows by one step (no-op for
        non-adam tables or before the first application created them)."""
        opt = info.get("opt") or {}
        if opt.get("type") != "adam":
            return
        at = opt.get("attrs") or {}
        b1 = float(at.get("beta1", 0.9))
        b2 = float(at.get("beta2", 0.999))
        info["beta1_pow"] = info.get("beta1_pow", b1) * b1
        info["beta2_pow"] = info.get("beta2_pow", b2) * b2

    def _apply_sparse(self, table, ids, rows, advance_pows=True):
        """One optimizer application on this shard's touched rows
        (SelectedRows semantics: duplicates merged first — the moment
        updates are non-linear in g).  Mirrors the lazy/sparse branches
        of ops/optimizer_ops.py so a dist run matches the local
        is_sparse run row for row.  Called under self._lock.
        advance_pows=False defers the adam beta-pow advance to the
        caller (sync rounds advance once per round for EVERY table via
        _advance_pows, even row-less ones)."""
        info = self.sparse_tables[table]
        tbl = info["tbl"]
        opt = info.get("opt") or {}
        typ = opt.get("type", "sgd")
        at = opt.get("attrs") or {}
        ids = np.asarray(ids).reshape(-1)
        dirty = self._mig_dirty
        if dirty is not None and table in dirty:
            # delta handoff in flight: record which rows this (still
            # serving) application touches so the frozen final tail
            # ships only them.  Momentum's densified rule mutates EVERY
            # row (whole-table velocity decay) — fall back to a full
            # re-ship rather than under-ship.
            if typ == "momentum":
                dirty[table] = None
            elif dirty[table] is not None:
                dirty[table].update(int(x) for x in ids)
        # explicit second dim: -1 is ambiguous (ValueError) for 0 rows,
        # and rowless momentum decay feeds exactly that
        rows = np.asarray(rows, dtype=tbl.dtype).reshape(
            ids.size, tbl.shape[1])
        uids, inv = np.unique(ids, return_inverse=True)
        g = np.zeros((uids.size, tbl.shape[1]), tbl.dtype)
        np.add.at(g, inv, rows)
        lr = self._sparse_lr_value(info)
        if typ == "sgd":
            tbl[uids] -= lr * g
        elif typ == "adagrad":
            eps = float(at.get("epsilon", 1e-6))
            m = info.setdefault("moment", np.zeros_like(tbl))
            mn = m[uids] + g * g
            m[uids] = mn
            tbl[uids] -= lr * g / (np.sqrt(mn) + eps)
        elif typ == "momentum":
            # momentum_op.h SparseMomentumFunctor: densified rule over
            # EVERY shard row — untouched rows' velocity still decays
            mu = float(at.get("mu", 0.9))
            v = info.setdefault("velocity", np.zeros_like(tbl))
            g_dense = np.zeros_like(tbl)
            g_dense[uids] = g
            v *= mu
            v += g_dense
            if at.get("use_nesterov"):
                tbl -= lr * (g_dense + mu * v)
            else:
                tbl -= lr * v
        elif typ == "adam":
            b1 = float(at.get("beta1", 0.9))
            b2 = float(at.get("beta2", 0.999))
            eps = float(at.get("epsilon", 1e-8))
            m1 = info.setdefault("moment1", np.zeros_like(tbl))
            m2 = info.setdefault("moment2", np.zeros_like(tbl))
            b1p = info.setdefault("beta1_pow", b1)
            b2p = info.setdefault("beta2_pow", b2)
            lr_t = lr * np.sqrt(1.0 - b2p) / (1.0 - b1p)
            m1n = b1 * m1[uids] + (1.0 - b1) * g
            m2n = b2 * m2[uids] + (1.0 - b2) * g * g
            m1[uids], m2[uids] = m1n, m2n
            tbl[uids] -= lr_t * m1n / (np.sqrt(m2n) + eps)
            if advance_pows:
                # async mode: global beta pows advance per application
                # (the lazy adam rule, adam_op.h SelectedRows branch)
                info["beta1_pow"] = b1p * b1
                info["beta2_pow"] = b2p * b2
        else:
            raise ValueError("unknown sparse optimizer %r" % typ)

    def _h_send_sparse(self, table, ids, rows, trainer_id=0, step=None,
                       seq=None, pepoch=None):
        """Sparse optimizer update on this server's rows (SelectedRows
        grad).  Sync mode queues until the round barrier so the update
        sees this round's scheduled lr and all trainers' rows merge into
        ONE application (the reference's optimizer-sub-block-at-barrier
        semantics); async applies immediately.  `step` is the sync dense
        stream's fence token: a fenced replay of a round this server
        already folded (it survived in the restored snapshot) is dropped
        so its rows cannot leak into the NEXT round.

        `seq` (async fenced delivery, docs/FAULT_TOLERANCE.md): the
        per-(trainer, table) sequence token the transpiler-stamped async
        ops mint once per STEP (shipped to every server, empty chunks
        included, so seq doubles as the trainer's logical clock).  The
        fence is monotonic — sends are serial per trainer, so a seq at
        or below the durably-applied high-water is an at-least-once
        re-delivery and drops (`dup`); the reply acks the high-water so
        the client can prune its resend queue.  Applied non-empty chunks
        are journaled + fsync'd BEFORE the ack, making ack == durable.
        The seq also drives the bounded-staleness park: a trainer
        running more than FLAGS_async_staleness_bound ahead of the
        slowest live peer waits here until the laggard advances or
        departs."""
        ids = np.asarray(ids).reshape(-1)
        rows = np.asarray(rows)
        with self._cv:
            self._touch(trainer_id)
            self._freeze_wait_locked()
            tid = int(trainer_id)
            if tid in self._evicted:
                return {"ok": False, "evicted": True}
            if self.plan_spec is not None \
                    and table not in self.sparse_tables:
                # migrated-away sparse shard: the sender's routing
                # predates the flip — re-plan and re-ship to the owner
                self.counters["stale_plan_drops"] += 1
                return self._plan_reply_locked(
                    {"ok": True, "stale_plan": True,
                     "pepoch": self._plan_epoch})
            if self.sync_mode and self._stale_plan_locked(pepoch):
                # plan-epoch fence: rows scaled for a stale world must
                # not queue into a current-epoch round (the sender
                # re-plans and re-ships — see _h_send_bucket)
                return {"ok": True, "stale_plan": True,
                        "pepoch": self._plan_epoch}
            if (self.sync_mode and step is not None
                    and int(step) <= self._folded_send.get(tid, -1)):
                self.counters["dup_round_drops"] += 1
                return self._plan_reply_locked(
                    {"ok": True, "dup_round": True})
            if self.sync_mode:
                # keyed overwrite: a fenced replay of this round's chunk
                # replaces rather than double-queues (dist_ops ships one
                # chunk per (table, server) per step)
                self._pending_sparse[(tid, table)] = (ids, rows)
                return self._plan_reply_locked({"ok": True})
            # ---- async path ------------------------------------------
            key = (tid, str(table))
            if seq is not None:
                seq = int(seq)
                fence = self._sparse_fence.get(key, 0)
                if seq <= fence:
                    self.counters["dedup_drops"] += 1
                    return self._plan_reply_locked(
                        {"ok": True, "dup": True, "acked": fence})
                self._clock_update_locked(tid, seq)
                self._park_if_stale_locked(tid, seq)
                if tid in self._evicted:  # evicted while parked
                    return {"ok": False, "evicted": True}
            if ids.size:
                self._async_touched.add(table)
                self._apply_sparse(table, ids, rows)
                # durable BEFORE the ack; empty (clock-only) chunks skip
                # the journal — the fence is monotonic, so the restored
                # high-water tolerating their seq gap is safe
                self._journal_append_locked(
                    {"k": "s", "t": str(table), "i": ids, "r": rows,
                     "tid": tid, "q": seq})
            if seq is not None:
                # fence commit BEFORE the rotation check: a snapshot
                # capturing the applied chunk but not its fence would
                # let a re-delivery through post-restore (double apply)
                self._sparse_fence[key] = seq
            # rotation cadence runs for EVERY journaled chunk — unfenced
            # (hybrid-collective / legacy) streams journal too, and with
            # dense traffic riding the mesh nothing else would ever
            # bound the segment's growth
            self._journal_maybe_snapshot_locked()
            if seq is not None:
                return self._plan_reply_locked({"ok": True, "acked": seq})
        return {"ok": True}

    def _h_sparse_clocks(self, clocks, trainer_id=0):
        """Merged clock-only frame (async fenced mode): one RPC carries
        EVERY table whose chunk this step had no rows for this server —
        previously each shipped its own empty send_sparse, n_servers *
        n_tables tiny frames per async step.  Semantics are identical to
        the empty chunks this replaces: per-table fences advance
        monotonically (nothing is journaled — there is no data), the
        trainer's logical clock advances to the newest seq, and the
        bounded-staleness park applies exactly once for the frame."""
        with self._cv:
            self._touch(trainer_id)
            self._freeze_wait_locked()
            tid = int(trainer_id)
            if tid in self._evicted:
                return {"ok": False, "evicted": True}
            newest = 0
            for table, seq in sorted(dict(clocks).items()):
                key = (tid, str(table))
                seq = int(seq)
                if seq > self._sparse_fence.get(key, 0):
                    self._sparse_fence[key] = seq
                newest = max(newest, seq)
            if newest:
                self._clock_update_locked(tid, newest)
                self._park_if_stale_locked(tid, newest)
                if tid in self._evicted:  # evicted while parked
                    return {"ok": False, "evicted": True}
            return self._plan_reply_locked({"ok": True, "acked": newest})

    def _h_checkpoint_notify(self, dir=None, trainer_id=0):
        """Trainer-initiated checkpoint (checkpoint_notify_op.cc analog).
        Snapshots into the REQUESTED dir without adopting it — the
        server's own periodic checkpoints keep their configured home, so
        they never overwrite (or resurrect) a trainer serial dir."""
        with self._lock:
            ok = self.save_checkpoint(dir=dir)
        return {"ok": bool(ok), "round": self._round}

    def _h_complete(self, trainer_id=0):
        with self._cv:
            tid = int(trainer_id)
            departed = False
            if tid in self._live:
                self._live.discard(tid)
                self._completed.add(tid)
                departed = True
            elif (tid not in self._evicted and tid not in self._completed
                    and self._live):
                # genuinely unknown id (legacy callers used a bare
                # count): treat it as one departure so done-detection
                # still converges.  A REPEATED complete (trainer exits
                # after send_complete_all, launcher also notifies) and an
                # evicted trainer's complete are already accounted for —
                # popping an arbitrary survivor would corrupt the barrier
                # denominator.
                self._live.pop()
                self._completed.add(tid)  # once: repeats must not re-pop
                departed = True
            self._tracked.pop(tid, None)
            # completion frees the staleness bound exactly like eviction
            # (the notify_all below wakes any parked fast peer)
            self._trainer_clock.pop(tid, None)
            # a departing trainer may unblock a pending round.  Its SEND
            # entry is kept (a clean departure's grads still count toward
            # the round it joined) but its FETCH entry is dropped: "I
            # already fetched" must not complete the fetch count while
            # survivors are still mid-fetch — that would reset
            # params_ready under their remaining gets
            self._fetch_barriers.discard(tid)
            self._send_bucket_counts.pop(tid, None)
            self._fetch_bucket_counts.pop(tid, None)
            self._send_step.pop(tid, None)
            self._send_seen.pop(tid, None)
            self._fetch_step.pop(tid, None)
            self._fetch_seen.pop(tid, None)
            # a parked joiner admits (boundary-guarded) before the
            # done-check: a completing survivor must not declare the job
            # over under a rejoiner
            self._admit_pending_joins_locked()
            if departed:
                # clean departure is a durable shrink: the survivors'
                # next rounds re-scale to the smaller world
                self._mark_plan_dirty_locked()
            if not self._live:
                self._done.set()
            if self.sync_mode and self._live:
                self._reeval_barriers_locked()
            self._cv.notify_all()
        return {"ok": True}

    @property
    def _live_trainers(self):
        """Back-compat count view of the live set."""
        return len(self._live)

    def wait_done(self, timeout=None):
        return self._done.wait(timeout)


def run_pserver(program, scope, executor=None):
    """Execute a transpiled pserver program: start the VarServer on the
    listen_and_serv op's endpoint, block until all trainers complete.

    This is what Executor.run does when it sees a `listen_and_serv` op —
    the analog of ListenAndServOp::RunImpl.
    """
    from .rpc import make_var_server

    listen_op = None
    for op in program.global_block().ops:
        if op.type == "listen_and_serv":
            listen_op = op
            break
    assert listen_op is not None, "no listen_and_serv op in pserver program"
    a = listen_op.attrs

    shard_programs = [framework.Program.from_json(s) for s in a["optimize_programs"]]
    lr_program = (
        framework.Program.from_json(a["lr_program"]) if a.get("lr_program") else None
    )

    # materialize block vars from the full vars the startup program created
    for src, block_name, begin, end in a["slice_plan"]:
        var = scope.find_var(src)
        if var is None:
            raise RuntimeError(
                "pserver startup did not create %s (run get_startup_program "
                "through this executor first)" % src
            )
        flat = np.asarray(var).reshape(-1)
        scope.set(block_name, np.ascontiguousarray(flat[begin:end]))
    for name in a.get("whole_vars", []):
        if scope.find_var(name) is None:
            raise RuntimeError("pserver startup did not create %s" % name)

    # distributed lookup-table shards: slice this server's rows (g%N) out
    # of the full table the startup program initialized.  Spec row:
    # [shard, src, server_idx, n_servers, lr] (+ optional opt dict)
    sparse_tables = {}
    for spec in a.get("sparse_tables", []):
        shard_name, src, server_idx, n_servers, lr = spec[:5]
        opt = spec[5] if len(spec) > 5 else None
        var = scope.find_var(src)
        if var is None:
            raise RuntimeError(
                "pserver startup did not create lookup table %s" % src
            )
        full = np.array(var)
        sparse_tables[shard_name] = {
            "tbl": np.ascontiguousarray(full[int(server_idx)::int(n_servers)]),
            "lr": float(lr) if lr is not None else None,
            "opt": dict(opt) if opt else {"type": "sgd", "attrs": {}},
        }

    import os as _os

    # checkpoint wiring: attr from the transpiler config, else the
    # PADDLE_PSERVER_CKPT_DIR env contract (test/ops harness)
    ckpt_dir = a.get("checkpoint_dir") or _os.environ.get(
        "PADDLE_PSERVER_CKPT_DIR"
    )
    ckpt_every = int(
        a.get("checkpoint_every")
        or _os.environ.get("PADDLE_PSERVER_CKPT_EVERY", 1)
    )
    try:
        server_idx = [s.strip() for s in _os.environ.get(
            "PADDLE_PSERVER_EPS", ""
        ).split(",")].index(a["endpoint"])
    except ValueError:
        if a.get("elastic"):
            # elastic-grown server OUTSIDE the base endpoint list: its
            # checkpoint/journal files must not collide with base
            # server 0's — key them by port (unique per live server)
            server_idx = int(a["endpoint"].rsplit(":", 1)[1])
        else:
            server_idx = 0

    # live shard migration config: the declarative plan spec (when the
    # transpiler stamped one) + this server's endpoint + the pserver
    # world — PADDLE_PSERVER_EPS is the BASE world; a snapshot restore
    # or a migrate_commit moves it forward
    plan_spec = a.get("plan_spec")
    ps_world = [e.strip() for e in _os.environ.get(
        "PADDLE_PSERVER_EPS", "").split(",") if e.strip()]
    if not ps_world and plan_spec:
        ps_world = list(plan_spec.get("endpoints") or [])
    sparse_shard_idx = {spec[0]: int(spec[2])
                       for spec in a.get("sparse_tables", [])}

    service = ParameterServer(
        shard_programs,
        dict(a["grad_to_shard"]),
        lr_program=lr_program,
        num_trainers=int(a["trainers"]),
        sync_mode=bool(a["sync_mode"]),
        scope=scope,
        sparse_tables=sparse_tables,
        sparse_lr=float(a.get("sparse_lr", 0.01)),
        checkpoint_dir=ckpt_dir,
        checkpoint_every=ckpt_every,
        server_idx=server_idx,
        plan_spec=plan_spec,
        endpoint=a["endpoint"],
        ps_world=ps_world or None,
        sparse_shard_idx=sparse_shard_idx,
    )
    if (service._journal_enabled() and plan_spec
            and int((plan_spec.get("flags") or {})
                    .get("comm_bucket_bytes", 0)) <= 0):
        import sys as _sys

        # satellite: surface the reduced guarantee at STARTUP, not just
        # in the docs — the legacy per-var wire journals but cannot
        # fence, so exactly-once across SIGKILL does not hold here
        service._unfenced_async = True
        _sys.stderr.write(
            "PSERVER WARNING: async journal armed on the legacy "
            "per-variable wire (comm_bucket_bytes=0): applied updates "
            "are crash-durable but UNFENCED — an RPC retry straddling "
            "a restart can double-apply.  Set FLAGS_comm_bucket_bytes>0 "
            "for exactly-once delivery (docs/FAULT_TOLERANCE.md)\n")
    restored = service.load_checkpoint()
    if restored is not None:
        print("PSERVER RESTORED round=%d incarnation=%d"
              % (restored, service.incarnation), flush=True)
    elif service._journal_enabled():
        # journal armed, cold start: land a BIRTH snapshot (synchronous,
        # before the listener opens, so no update can precede it).  The
        # journal records deltas; without a persisted base a restore
        # before the first cadence snapshot would replay them onto a
        # freshly re-initialized table — only bit-identical to the dead
        # incarnation's when the startup init happens to be seeded.
        service.save_checkpoint()
    server = make_var_server(a["endpoint"], service).start()
    try:
        service.wait_done()
    finally:
        server.shutdown()
        # recovery observability: the server-side sibling of the
        # trainers' COUNTERS line (distinct prefix — a reader summing
        # trainer COUNTERS lines must not fold these in)
        import json as _json

        with service._cv:
            phases = service._phases_snapshot_locked()
            plan_epoch = service._plan_epoch
        print("PSERVER-STATS " + _json.dumps(
            dict(service.counters, round=service._round,
                 incarnation=service.incarnation,
                 async_sends=service._async_sends,
                 plan_epoch=plan_epoch, phases=phases)), flush=True)
