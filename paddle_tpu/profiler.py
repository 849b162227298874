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
"""

import contextlib
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = [
    "RecordEvent",
    "record_event",
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
