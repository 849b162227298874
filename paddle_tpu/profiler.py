"""Profiler (python/paddle/fluid/profiler.py + platform/profiler.{h,cc}
analog).

The reference wraps every op run in RecordEvent scopes and correlates CUPTI
device activity into a chrome-trace timeline (tools/timeline.py).  Here the
step is one compiled program: device-side tracing delegates to jax.profiler
(XLA/xplane — TensorBoard readable), in which every device op carries the
`<op_role>/<op type>/<index>` scope of the Fluid op it came from
(core/trace.py), and host scopes are RecordEvent spans (the Executor's
boundaries + user ranges) that land on the host plane of that same trace,
on its clock, and are additionally dumped as chrome-trace JSON so
`profiler(state)`-style workflows keep their artifact.

Work that happens once a process or once a compile (the import, building
a program, a cache miss's analysis / trace / lowering / compile) is a
`phase`: always recorded, in the set-up ledger `phases()`, beside the
`counters()` of sites too frequent for a record each.  A step opens none.
"""

import contextlib
import json
import os
import threading
import time

import jax.monitoring
from jax.profiler import TraceAnnotation

__all__ = [
    "RecordEvent",
    "record_event",
    "phase",
    "phases",
    "counted",
    "counters",
    "profiler",
    "start_profiler",
    "stop_profiler",
    "reset_profiler",
    "cuda_profiler",
    "tpu_profiler",
    "per_op_timeline",
    "comm_compute_split",
    "COMM_OPS",
    "PHASE_CATS",
]

_events = []
_events_lock = threading.Lock()
_enabled = False
_trace_dir = None

# op types whose host time is DCN communication, not compute — the
# per_op_timeline comm/compute split (RPC sends/recvs/barriers plus the
# bucketed/pipelined variants and the sparse-table verbs)
COMM_OPS = frozenset((
    "send", "recv", "send_bucket", "recv_bucket", "send_barrier",
    "fetch_barrier", "prefetch", "send_sparse", "checkpoint_notify",
))


class RecordEvent:
    """RAII span (platform/profiler.h:73 RecordEvent parity), collected in
    two places from one enter/exit:

      * as `paddle_tpu:<name>` on the host plane of any running JAX
        trace (`jax.profiler.TraceAnnotation`), i.e. on the clock of the
        device ops in the same `.xplane.pb` — whoever started the trace:
        `profiler(..., trace_dir=)`, `tpu_profiler`, or a plain
        `jax.profiler.start_trace`.  `args` become the event's stats;
      * under its bare name in this module's chrome-trace list while
        `start_profiler` .. `stop_profiler` is collecting (what
        `profiler()` prints and tools/timeline.py merges).

    When neither collects, a span reads no clock and records nothing.
    The clocks: the trace's spans are on the profiler's own clock, with
    the device ops; the chrome list is on `time.time()`, so that
    tools/timeline.py can merge the lists of several workers; a `phase`
    record is on `time.perf_counter()`, a process's own clock.
    `cat` categorizes the span for comm-vs-compute attribution in the
    chrome trace ("comm" for RPC sends/recvs, "feed" for host->device
    uploads; unset spans are compute/host work)."""

    __slots__ = ("name", "cat", "args", "t0", "_annotation")

    def __init__(self, name, cat=None, **args):
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = None
        self._annotation = None

    def __enter__(self):
        self.t0 = time.time() if _enabled else None
        if TraceAnnotation.is_enabled():
            self._annotation = TraceAnnotation(
                "paddle_tpu:" + self.name, **self.args)
            self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        if _enabled and self.t0 is not None:
            ev = {
                "name": self.name,
                "ph": "X",
                "ts": self.t0 * 1e6,
                "dur": (time.time() - self.t0) * 1e6,
                "pid": os.getpid(),
                "tid": threading.get_ident() % 10000,
            }
            if self.cat:
                ev["cat"] = self.cat
            if self.args:
                ev["args"] = self.args
            with _events_lock:
                _events.append(ev)
        return False


@contextlib.contextmanager
def record_event(name, cat=None):
    with RecordEvent(name, cat=cat):
        yield


# ---- the set-up ledger -----------------------------------------------------
# What happens once a process or once a compile is recorded always: two
# clock reads and one append a phase, some tens of phases a run, none of
# them on a steady step's path.  PERF.md section 3 names the metric that
# reads each phase and counter.
PHASE_LIMIT = 4096  # records kept; later ones are counted as phases_dropped

_ledger_lock = threading.Lock()
_phases = []
_counters = {}  # name -> [calls, seconds]
_open = threading.local()  # .stack: this thread's open phases


def _open_stack():
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


def _count(name, seconds=0.0):
    with _ledger_lock:
        c = _counters.get(name)
        if c is None:
            c = _counters[name] = [0, 0.0]
        c[0] += 1
        c[1] += seconds


def counters():
    """{name: {"calls", "seconds"}} of the process so far: the sites too
    frequent for a record each (`counted`), what JAX reported of compiles
    under no `trace_compile` phase (`compile.*`), and `phases_dropped`."""
    with _ledger_lock:
        return {n: {"calls": c[0], "seconds": c[1]}
                for n, c in _counters.items()}


@contextlib.contextmanager
def counted(name):
    """Count one call of a site under `name` in `counters()`, with the
    time it took."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        _count(name, time.perf_counter() - t0)


def phases():
    """The set-up ledger: one record a phase, in the order they opened:
    {"name", "t0", "t1" (None while open), "args", "thread", "depth",
    "counters", "counters_end"}.  The times are `time.perf_counter()`;
    `depth` is how many phases the opening thread had open; the two
    `counters` are `counters()` as the phase opened and as it last
    closed, so that a reader can tell what was counted before, inside
    and after it by order alone.  Copies: a caller may keep them."""
    with _ledger_lock:
        return [dict(r, args=dict(r["args"])) for r in _phases]


class phase:
    """Span of work that happens once a process or once a compile, never
    once a step.  Unlike a RecordEvent it always records, in `phases()`;
    it also enters a RecordEvent of the same name and `args`, so under a
    running JAX trace it is a `paddle_tpu:<name>` span like every other.

    `t0` is for a start that was read before this module could be
    imported.  Entering a phase object again resumes its record: the new
    span is a second `paddle_tpu:<name>` event, `t1` moves to its end,
    and the record stays one (a compile's analysis at the cache miss and
    its first call: core/trace.ExecutionCache.miss)."""

    __slots__ = ("name", "args", "record", "_t0", "_event", "_outermost")

    def __init__(self, name, t0=None, **args):
        self.name = name
        self.args = args
        self.record = None
        self._t0 = t0
        self._event = None
        self._outermost = []  # _on_compile_span's: (start, field, seconds)

    def __enter__(self):
        stack = _open_stack()
        rec = self.record
        if rec is None:
            now = time.perf_counter()
            rec = self.record = {
                "name": self.name,
                "t0": now if self._t0 is None else self._t0, "t1": None,
                "args": dict(self.args),
                "thread": threading.get_ident(), "depth": len(stack),
                "counters": counters(), "counters_end": None,
            }
            with _ledger_lock:
                kept = len(_phases) < PHASE_LIMIT
                if kept:
                    _phases.append(rec)
            if not kept:
                _count("phases_dropped")
        stack.append(self)
        self._event = RecordEvent(self.name, **self.args)
        self._event.__enter__()
        return self

    def __exit__(self, *exc):
        self._event.__exit__(*exc)
        _open_stack().remove(self)
        self.record["counters_end"] = counters()
        self.record["t1"] = time.perf_counter()
        return False


# What JAX 0.9.0 announces of the inside of a compile (jax.monitoring),
# by the field of a `trace_compile` record it adds to.  The first three
# come as time spans on one clock (time.time()) and nest: tracing a step
# traces every inner jit, and a lowering that computes a constant eagerly
# compiles inside the outer trace.  Only the outermost spans count, so
# the three fields never sum to more than the record's length.
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


def _compiling():
    """The calling thread's open `trace_compile` phase, innermost first."""
    for ph in reversed(getattr(_open, "stack", None) or ()):
        if ph.name == "trace_compile":
            return ph
    return None


def _announced(field, seconds=None):
    """One announcement (a duration, or with no `seconds` an event to
    count) goes to the calling thread's open `trace_compile` record, or
    with none open to the process counter `compile.<field>`.  Returns the
    open phase."""
    ph = _compiling()
    if ph is None:
        _count("compile." + field, seconds or 0.0)
    else:
        args = ph.record["args"]
        args[field] = args.get(field, 0) + (1 if seconds is None else seconds)
    return ph


def _on_compile_span(event, start, end, **_):
    field = _COMPILE_SPANS.get(event)
    if field is None:
        return
    ph = _announced(field, end - start)
    if ph is not None:
        args, outermost = ph.record["args"], ph._outermost
        while outermost and outermost[-1][0] >= start:  # nested in this one
            _, inner_field, inner_s = outermost.pop()
            args[inner_field] -= inner_s
        outermost.append((start, field, end - start))


def _on_compile_duration(event, seconds, **_):
    if event == _CACHE_READ:
        _announced("cache_read_s", seconds)


def _on_compile_event(event, **_):
    field = _CACHE_EVENTS.get(event)
    if field is not None:
        _announced(field)


# registered once a process: a cached executable's call announces nothing,
# so a steady step never reaches them
jax.monitoring.register_event_time_span_listener(_on_compile_span)
jax.monitoring.register_event_duration_secs_listener(_on_compile_duration)
jax.monitoring.register_event_listener(_on_compile_event)


def reset_profiler():
    with _events_lock:
        _events.clear()


def start_profiler(state="All", trace_dir=None):
    """state in {CPU, GPU/TPU, All} (API parity; device tracing is xplane)."""
    global _enabled, _trace_dir
    _enabled = True
    _trace_dir = trace_dir
    if state in ("GPU", "TPU", "All") and trace_dir:
        import jax

        jax.profiler.start_trace(trace_dir)


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    """Stop; write host spans as chrome trace json + stop device trace."""
    global _enabled
    _enabled = False
    if _trace_dir:
        import jax

        try:
            jax.profiler.stop_trace()
        except RuntimeError:
            pass
    with _events_lock:
        evs = list(_events)
    if profile_path:
        with open(profile_path + ".json" if not profile_path.endswith(".json") else profile_path, "w") as f:
            json.dump({"traceEvents": evs}, f)
    # aggregate table (EnableProfiler report parity)
    agg = {}
    for e in evs:
        a = agg.setdefault(e["name"], [0, 0.0])
        a[0] += 1
        a[1] += e["dur"] / 1e3
    rows = sorted(agg.items(), key=lambda kv: -kv[1][1])
    if rows:
        print("%-40s %8s %12s" % ("Event", "Calls", "Total(ms)"))
        for name, (calls, total) in rows[:30]:
            print("%-40s %8d %12.2f" % (name[:40], calls, total))
    return rows


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile", trace_dir=None):
    """`with profiler('All'):` context (fluid.profiler.profiler :221 parity)."""
    reset_profiler()
    start_profiler(state, trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


def per_op_timeline(program, feed, scope=None, path=None, warmup=1,
                    block_idx=0):
    """Per-op timeline of an EAGER, UNFUSED re-run of each lowering
    (device_tracer.h:26,49 + tools/timeline.py:160 capability,
    re-expressed for a compile-first engine) — a diagnostic
    interpretation pass, NOT the compiled step: XLA fuses the block into
    one executable whose ops this never sees, and both columns are host
    clocks.  Each op's lowering runs on concrete arrays, timed twice —
    cold (host dispatch + compile + device) and warm (the "device"
    column: a re-run under block_until_ready, still a host clock around
    an op-at-a-time program).  Both spans share a correlation id per op
    (the reference's CUPTI correlation contract) and land in ONE
    chrome-trace JSON with separate tracks.  Returns the rows [(op_type,
    idx, host_ms, device_ms)] sorted by the warm time.

    For where the compiled step's device time goes, trace it
    (`tpu_profiler` / `profiler(trace_dir=)`): every device op carries
    the `<op_role>/<op type>/<index>` scope of the Fluid op it was
    lowered from (core/trace.py; Executor.compiled_hlo shows them), and
    benchmark/readers/program_profile.py reduces such a trace by scope.

    Flat blocks only (while/cond sub-blocks time as their parent op would
    under the real executor — use the aggregate profiler for those).
    """
    import jax
    import numpy as np

    from .core.registry import OPS, LowerCtx, get_op, lower_grad_op
    from .core.scope import global_scope
    from .core.selected_rows import SelectedRows, densify_maybe

    scope = scope or global_scope()
    blk = program.block(block_idx)
    env = {}
    for k, v in (feed or {}).items():
        env[k] = jax.numpy.asarray(np.asarray(v))
    ctx = LowerCtx(rng_key=jax.random.PRNGKey(0), scope=scope)
    events = []
    rows = []
    t_base = time.time()

    for idx, op in enumerate(blk.ops):
        if op.type in ("feed", "fetch", "read", "create_py_reader"):
            continue
        if op.type in ("while", "cond"):
            raise ValueError(
                "per_op_timeline supports flat blocks; '%s' at op %d owns "
                "a sub-block" % (op.type, idx))
        ctx.op_idx = idx
        ctx.block = blk
        opdef = OPS.get(op.type)
        ins = {}
        for slot, names in op.inputs.items():
            vals = []
            for n in names:
                if n in env:
                    vals.append(env[n])
                elif scope.has_var(n):
                    vals.append(jax.numpy.asarray(scope.find_var(n)))
                else:
                    raise RuntimeError(
                        "per_op_timeline: op %s reads %s which is neither "
                        "fed nor in scope" % (op.type, n))
            ins[slot] = vals
        # mirror the executor's SelectedRows contract: non-aware ops see
        # the densified tensor
        if any(isinstance(v, SelectedRows)
               for vs in ins.values() for v in vs) and not (
                   opdef is not None and opdef.handles_selected_rows):
            ins = {s_: [densify_maybe(v) for v in vs]
                   for s_, vs in ins.items()}

        def run_once():
            if op.type.endswith("_grad") and "__fwd_type__" in op.attrs \
                    and op.type not in OPS:
                out = lower_grad_op(ctx, op, ins, op.attrs)
            else:
                out = get_op(op.type).lower(ctx, ins, op.attrs)
            jax.block_until_ready(
                [v for vs in out.values() for v in vs if v is not None])
            return out

        t0 = time.time()
        outs = run_once()
        host_ms = (time.time() - t0) * 1e3
        dev_ms = host_ms
        # side-effect ops (RPC sends, barriers, checkpoint notifies) must
        # run exactly once — a warm re-run would duplicate the effect
        if warmup and not (opdef is not None and opdef.side_effect):
            t0 = time.time()
            for _ in range(warmup):
                outs = run_once()
            dev_ms = (time.time() - t0) * 1e3 / warmup
        ts = (time.time() - t_base) * 1e6
        cat = "comm" if op.type in COMM_OPS else "compute"
        for tid, name, dur in ((1, "host", host_ms), (2, "device", dev_ms)):
            events.append({
                "name": "%s#%d" % (op.type, idx), "ph": "X", "cat": cat,
                "ts": ts, "dur": dur * 1e3, "pid": os.getpid(), "tid": tid,
                "args": {"correlation": idx, "track": name},
            })
        rows.append((op.type, idx, host_ms, dev_ms))
        for slot, names in op.outputs.items():
            vals = outs.get(slot)
            if vals is None:
                continue
            for n, v in zip(names, vals):
                if n and v is not None:
                    env[n] = v

    if path:
        meta = [
            {"ph": "M", "pid": os.getpid(), "tid": 1, "name": "thread_name",
             "args": {"name": "host (dispatch+compile)"}},
            {"ph": "M", "pid": os.getpid(), "tid": 2, "name": "thread_name",
             "args": {"name": "device (warm re-run)"}},
        ]
        with open(path, "w") as f:
            json.dump({"traceEvents": meta + events}, f)
    return sorted(rows, key=lambda r: -r[3])


# RecordEvent categories that refine the comm bucket: wire
# serialization (rpc._send_msg), grad compression (dist_ops
# wire_compress) and the pserver's fused optimize apply
# (ps_server._run_round).  Spans with these cats are attributed to
# their own phase by comm_compute_split instead of lumping into comm.
# The serving engine's loop phases (serving/engine.py) ride the same
# mechanism: admit (admission + slot reset), prefill / decode (the
# pooled model dispatch, tagged by whether any slot is prefilling),
# sample (host-side per-request token selection) — so
# comm_compute_split(events=...) shows where serve time goes.
PHASE_CATS = ("serialize", "compress", "apply",
              "admit", "prefill", "decode", "sample")


def comm_compute_split(rows, events=None):
    """Attribute per_op_timeline rows to DCN communication vs compute:
    returns {"comm_ms", "compute_ms", "comm_fraction"} over the host
    track — where the step's wall time actually goes when deciding
    whether bucketing/overlap or kernels are the bottleneck.

    When cat-tagged phase spans were recorded (`events`; defaults to the
    profiler's captured span list), the split additionally reports
    serialize/compress/apply milliseconds — the wire-compression and
    fused-apply phases — so those show up as their own lines instead of
    disappearing into comm."""
    comm = sum(r[2] for r in rows if r[0] in COMM_OPS)
    compute = sum(r[2] for r in rows if r[0] not in COMM_OPS)
    total = comm + compute
    out = {
        "comm_ms": round(comm, 3),
        "compute_ms": round(compute, 3),
        "comm_fraction": round(comm / total, 4) if total else 0.0,
    }
    if events is None:
        with _events_lock:
            events = list(_events)
    for cat in PHASE_CATS:
        ms = sum(e["dur"] for e in events if e.get("cat") == cat) / 1e3
        if ms:
            out[cat + "_ms"] = round(ms, 3)
    return out


@contextlib.contextmanager
def tpu_profiler(output_dir):
    """Device-side trace via jax.profiler (cuda_profiler :39 analog)."""
    import jax

    jax.profiler.start_trace(output_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


cuda_profiler = tpu_profiler  # API alias for reference scripts
