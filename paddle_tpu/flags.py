"""Runtime flag registry (the gflags surface, SURVEY §5.6).

The reference defines ~31 gflags in C++ and exports an allowlist to Python
via __init__.py __bootstrap__ (:85) -> core.init_gflags.  Here the registry
is the single source of truth; values load from the environment at import:

* `FLAGS_<name>=value` env vars (the reference's exact contract), or
* `PADDLE_TPU_FLAGS="--name=value --other=v"` batch form.

Wired flags: check_nan_inf (executor fetch scan), benchmark (per-run
timing log), rpc_deadline / max_retry (RPC client), enable_rpc_profiler
(RecordEvent spans around RPC calls), heartbeat_interval /
eviction_deadline (trainer liveness + pserver barrier eviction,
docs/FAULT_TOLERANCE.md), async_journal / async_staleness_bound /
sparse_hot_rows / sparse_hot_ttl (durable async sparse: write-ahead
journal, bounded staleness, trainer-side hot-row prefetch cache —
docs/FAULT_TOLERANCE.md "Durable async sparse").  The remaining knobs
are accepted
for script compatibility and are no-ops under XLA (their help text says
so) — memory budgeting belongs to PJRT and fusion to the compiler.

Liveness-pair validation: eviction_deadline must exceed
heartbeat_interval, or every healthy trainer would miss its own liveness
deadline between beats (a self-evicting job).  The registry validates the
pair at load time and on set_flags(), warning and CLAMPING the deadline
to 3x the interval instead of silently configuring a broken job.

Self-healing knobs that are NOT FLAGS_: the supervisor restart policy
(--supervise / --max-restarts / --restart-window / --restart-backoff /
--ckpt-dir) is per-launch CLI surface on paddle_tpu.distributed.launch,
and pserver incarnation numbers are minted automatically per start
(persisted next to the checkpoint) — see docs/FAULT_TOLERANCE.md.
"""

import os

__all__ = ["DEFINE_flag", "get_flag", "set_flags", "flag_items"]

_flags = {}


class _Flag:
    __slots__ = ("name", "value", "default", "help")

    def __init__(self, name, default, help):
        self.name = name
        self.default = default
        self.value = default
        self.help = help


def _coerce(default, raw):
    if isinstance(default, bool):
        return str(raw).lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


def DEFINE_flag(name, default, help=""):
    f = _Flag(name, default, help)
    env = os.environ.get("FLAGS_" + name)
    if env is not None:
        f.value = _coerce(default, env)
    _flags[name] = f
    return f


def get_flag(name):
    return _flags[name].value


def set_flags(mapping):
    """dict name->value, applied with type coercion (init_gflags analog)."""
    for name, value in mapping.items():
        key = name[len("FLAGS_"):] if name.startswith("FLAGS_") else name
        if key not in _flags:
            raise KeyError("unknown flag %s (known: %s)" % (key, sorted(_flags)))
        f = _flags[key]
        f.value = _coerce(f.default, value)
    _validate_liveness_pair()


def _validate_liveness_pair():
    """eviction_deadline <= heartbeat_interval configures a SELF-EVICTING
    job: a healthy trainer goes 'silent' for one full interval between
    beats, so the deadline must comfortably exceed it.  Warn and clamp
    to 3x the interval (one lost beat + scheduling slack) rather than
    letting the misconfiguration eat the cluster at the first barrier."""
    if "eviction_deadline" not in _flags or "heartbeat_interval" not in _flags:
        return  # registry still loading
    hb = _flags["heartbeat_interval"].value
    ev = _flags["eviction_deadline"]
    if hb > 0 and ev.value <= hb:
        import sys

        clamped = 3.0 * float(hb)
        sys.stderr.write(
            "WARNING: FLAGS_eviction_deadline=%.3g <= "
            "FLAGS_heartbeat_interval=%.3g would evict healthy trainers "
            "between beats; clamping eviction_deadline to %.3g\n"
            % (ev.value, hb, clamped))
        ev.value = clamped


def flag_items():
    return {name: f.value for name, f in sorted(_flags.items())}


def _parse_batch_env():
    batch = os.environ.get("PADDLE_TPU_FLAGS", "")
    for tok in batch.split():
        if tok.startswith("--") and "=" in tok:
            k, v = tok[2:].split("=", 1)
            if k in _flags:
                f = _flags[k]
                f.value = _coerce(f.default, v)


# ---- the reference's knob surface (CMakeLists/bootstrap allowlist) -------
DEFINE_flag("check_nan_inf", False,
            "scan every fetched value for NaN/Inf and raise (operator.cc:688)")
DEFINE_flag("benchmark", False, "log wall time of every Executor.run")
DEFINE_flag("eager_delete_tensor_gb", -1.0,
            "compat no-op: XLA frees temps inside the step; rw state is "
            "donated unconditionally")
DEFINE_flag("fraction_of_gpu_memory_to_use", 0.92,
            "HBM budget fraction: forwarded to the XLA client allocator "
            "(memory.apply_memory_fraction) when set via FLAGS_... env "
            "before the first backend init")
DEFINE_flag("init_allocated_mem", False, "compat no-op under XLA")
DEFINE_flag("free_idle_memory", False, "compat no-op under XLA")
DEFINE_flag("paddle_num_threads", 1, "compat no-op (XLA owns threading)")
DEFINE_flag("dist_threadpool_size", 0,
            "compat no-op (pserver threads are per-connection)")
DEFINE_flag("rpc_deadline", 180000, "RPC timeout in ms (grpc deadline)")
DEFINE_flag("max_retry", 30, "RPC connect retries")
DEFINE_flag("heartbeat_interval", 2.0,
            "trainer->pserver liveness heartbeat period in seconds; a "
            "background sender starts with the first pserver RPC "
            "(0 disables heartbeats and therefore eviction)")
DEFINE_flag("eviction_deadline", 20.0,
            "seconds without any contact (heartbeat or verb) after which "
            "a heartbeat-tracked trainer is declared dead and evicted "
            "from the sync round — pending barriers re-evaluate against "
            "the surviving live set instead of hanging forever")
DEFINE_flag("enable_rpc_profiler", False, "RecordEvent spans around RPC")
DEFINE_flag("comm_bucket_bytes", 4 * 1024 * 1024,
            "size cap (bytes) for coalesced grad/param buckets in pserver "
            "mode: DistributeTranspiler groups small blocks into buckets "
            "and each bucket ships as ONE rpc frame per pserver instead "
            "of one round trip per variable (0 restores the legacy "
            "per-variable send/recv ops)")
DEFINE_flag("comm_wire_dtype", "float32",
            "wire dtype for dense bucket grads and fetched params on the "
            "pserver path: 'float32' (default — byte-identical legacy "
            "frames, bit-exact dist-vs-local parity) or 'bfloat16' (halves "
            "comm bytes; the trainer casts grads at the RPC boundary and "
            "the pserver casts fetched params in its replies, both "
            "decompressed back to the original dtype at decode).  The "
            "transpiler stamps the value into the bucket plan so both "
            "ends agree; the legacy per-variable ops "
            "(FLAGS_comm_bucket_bytes=0) always ship full precision")
DEFINE_flag("comm_grad_int8", False,
            "int8 + error-feedback wire compression for dense bucket "
            "grads (quarter-size frames): each block ships as int8 with a "
            "per-block scale, the quantization residual is kept "
            "TRAINER-side and added into the same block's grad next "
            "round, so the quantization error is corrected over time "
            "instead of accumulating (an approximation — see "
            "docs/PERFORMANCE.md).  Applies to grads only; fetched "
            "params follow FLAGS_comm_wire_dtype")
DEFINE_flag("ps_fused_apply", True,
            "pserver sync rounds apply the optimizer with ONE jitted "
            "fused call per (optimizer, dtype) group of shard blocks "
            "(blocks padded + stacked, lr read once per round) instead "
            "of one executor program run per block; shard programs the "
            "fuser cannot prove equivalent fall back to the per-block "
            "path automatically (0 disables the fused path entirely)")
DEFINE_flag("async_journal", True,
            "async pserver mode: append every applied sparse chunk / dense "
            "bucket to a crc-framed, fsync'd write-ahead journal next to "
            "the checkpoint (rotated at each snapshot).  A restarted "
            "incarnation replays journal-after-snapshot, so an async "
            "restart loses ZERO applied updates; corrupt/truncated tail "
            "records are skipped cold with a counter, like corrupt "
            "snapshots.  Needs a checkpoint dir; 0 restores the old "
            "lose-since-last-checkpoint behavior")
DEFINE_flag("async_staleness_bound", 0,
            "async pserver mode: park pushes/prefetches from a trainer "
            "whose logical clock (its per-table send_sparse seq tokens) "
            "runs more than this many steps ahead of the slowest live "
            "peer, releasing when the laggard catches up or departs "
            "(eviction/complete frees the bound).  0 = unbounded — the "
            "pre-bound fire-and-forget behavior")
DEFINE_flag("sparse_hot_rows", 0,
            "async pserver mode: trainer-side hot-row cache capacity (rows "
            "per table) for distributed-lookup prefetch.  Hits skip the "
            "prefetch RPC; pushed grads update the cached copy through "
            "the table's own optimizer rule (sgd mirrors exactly), and "
            "entries refresh from the server every "
            "FLAGS_sparse_hot_ttl steps so multi-trainer drift is "
            "corrected instead of accumulating.  Only engages where the "
            "mirror is exact: sgd, constant lr, uncompressed f32 sparse "
            "wire (a bf16 wire means the server applies DECODED grads "
            "the client does not hold).  0 disables the cache")
DEFINE_flag("sparse_hot_ttl", 8,
            "steps a hot-row cache entry may serve before it must be "
            "re-fetched from its pserver (the drift-correction refresh "
            "for FLAGS_sparse_hot_rows)")
DEFINE_flag("elastic_replan", True,
            "elastic autoscaling (docs/FAULT_TOLERANCE.md): trainers "
            "re-derive their bucket/shard plan at runtime (transpiler."
            "derive_plan over the program-carried plan spec) when a "
            "pserver mints a new plan epoch — membership changed "
            "durably — correcting the baked 1/N grad scale to the live "
            "world and fencing stale-epoch frames like stale "
            "incarnations.  For an unchanged world the re-derived plan "
            "is bit-identical to the transpile-time plan and the "
            "correction is exactly 1.0 (skipped), so static jobs are "
            "unaffected.  0 pins the transpile-time plan forever (the "
            "pre-elastic behavior: a dead trainer leaves the job "
            "under-scaled, an added one cannot contribute)")
DEFINE_flag("comm_inflight", 4,
            "window of in-flight bucket RPCs per pserver endpoint: bucket "
            "N+1 serializes and sends while bucket N is on the wire; "
            "send_barrier / the next recv drains the window (1 = fully "
            "serial, the pre-pipelining behavior)")
DEFINE_flag("feed_prefetch", 2,
            "depth of the reader.feed_prefetch double buffer: batch N+1 "
            "is device_put on a background thread while step N computes "
            "(0 disables staging; the decorator passes batches through)")
DEFINE_flag("cudnn_deterministic", False,
            "compat; XLA compilation is deterministic already")
DEFINE_flag("use_mkldnn", False, "compat no-op (XLA owns fusion)")
DEFINE_flag("hbm_budget_bytes", 0,
            "peak-activation HBM budget (bytes) for the rematerialization "
            "pass (transpiler.remat): model builders partition the forward "
            "program into checkpoint segments at detected layer boundaries "
            "and greedily mark segments for recompute (jax.checkpoint) "
            "until the traced fwd+bwd peak-activation estimate "
            "(utils.memory_analysis) fits the budget.  Marked segments "
            "recompute the SAME ops in backward, so losses are "
            "bit-identical to the unremat program.  0 disables the pass "
            "(the builders' hp.recompute knob still remats every layer "
            "unconditionally)")
DEFINE_flag("program_tune_cache", "",
            "path of the persisted per-(program-signature, shape-bucket, "
            "device kind) PROGRAM knob decision cache consulted by "
            "transpiler.autotune.tune(): searched decisions (AMP on/off, "
            "remat segments, prng impl, steps-per-dispatch window) are "
            "written back atomically so later processes apply the tuned "
            "configuration without re-searching.  Empty = in-memory "
            "only for this process")
DEFINE_flag("program_autotune", True,
            "allow transpiler.autotune.tune() to SEARCH (clone the "
            "program per candidate knob setting, jit, and time synthetic "
            "steps) on a decision-cache miss.  0 = consult-only: misses "
            "return the all-defaults decision and never time anything "
            "(the CI regime, with a pinned FLAGS_program_tune_cache)")
DEFINE_flag("check_program", False,
            "static program verification (analysis.verify_program): "
            "apply_pass re-verifies the program after EVERY registry "
            "pass (verified-in => verified-out, the TVM pass-infra "
            "contract) and the executor verifies each program version "
            "once before its first compile — an ill-formed program "
            "fails loudly at the pass boundary with the pass and the "
            "offending op named, instead of at JAX trace time (or "
            "silently, the PR 12 half-applied-fold bug class).  ON in "
            "tests/CI (conftest + scripts/ci.sh arm it); OFF by default "
            "in production hot paths — disabled, the check is a single "
            "flag read, zero per-step cost")
DEFINE_flag("tpu_bf16_matmul", False,
            "reserved: AMP is the explicit contrib.mixed_precision."
            "rewrite_bf16() program rewrite, not a global flag yet")

_parse_batch_env()
_validate_liveness_pair()
