"""The compiled step seen from inside: every device op of a traced slice
joined to the Fluid op it came from, and the Executor's own spans on the
same clock.

What the program gives (paddle_tpu, from the PR that added this file):

  scopes  core/trace.py lowers every Fluid op under
          jax.named_scope("<op_role>/<op type>/<index in its block>"), so
          the optimized HLO (Executor.compiled_hlo) says in each
          instruction's metadata op_name where it came from:
          "jit(program_step)/backward/conv2d_grad/637/transpose(jvp())/...".
          Sub-block ops nest: "forward/while/5/.../forward/mul/2/...".
  spans   profiler.RecordEvent enters a TraceAnnotation "paddle_tpu:<name>"
          on the host plane of a running trace; Executor.run opens
          executor.run (outer, one per call) and inside it feed_upload,
          state_gather, executor_run (trace_compile inside on a first
          call), state_commit, fetch_to_host.
  steps   Executor.compiled_steps(program): feed and fetch names of the
          executable the window ran.

A program without them (the parent of that PR) has no compiled_steps:
profile() is None and every metric over it is left out.

The device trace names an op by its instruction text without metadata
(looked at by hand on a v5e: "XLA Ops" events carry only timing stats), so
each event's instruction name ("%fusion.85") is joined to the HLO text.  A
fusion goes to the scope of the matmul / convolution inside its fused
computation when it holds one (XLA fuses the optimizer update into the
weight-gradient matmul), else to its root's, its own, or the scope most of
its members share; an op whose instruction or scope cannot be found is
unattributed: path "".

_trace_slice of loops/train.py deletes its trace, so this reader takes a
slice of its own, once per traced run: work["trace_steps"] steady steps of
the measured executable, dispatched as the loop dispatches them (no block
but a read-back of the first fetch every work["readback_every"]-th step, so
the drain the window pays is in the slice), blocked on at both ends.  A
collective in flight between its -start and -done counts as busy here (it
is what the device waits for), where trace_reduce counts op events alone.

profile(ctx) -> None, or
  steps, window_ms, busy_ms, slice_step_ms   the steady window on device 0,
                  found as trace_reduce finds it
  scope_ms        {scope path: ms of busy time it owns}; an instant belongs
                  to the op that started last among those running, a
                  compute op before a collective, so the values sum to
                  busy_ms
  exposed_collective_share   % of busy time owned by collectives (sync, or
                  -start to -done), i.e. during which no other op runs
  idle_in_executor_share     % of idle time under a paddle_tpu:executor.run
  idle_gaps       the longest, each [innermost span covering most of it, ms,
                  {span: ms of the gap under it, "caller": under none}]
  calls           [{span name: ms inside one executor.run call}], with
                  "executor.run" itself and "other" (not under a child)
and one logged line "program_profile: {...}" (see _summary).
"""

import collections
import glob
import heapq
import json
import os
import re
import shutil
import statistics

# Program.op_role values (paddle_tpu/framework.py); a scope is
# "<role>/<op type>/<index>" between "/" or "(" ")" in an op_name
ROLES = ("forward", "backward", "optimize", "lrsched", "loss", "rpc")
SCOPE = re.compile(r"(?:^|[/(])((?:%s)/[\w.]+/\d+)(?=[/)]|$)"
                   % "|".join(ROLES))
SPAN_PREFIX = "paddle_tpu:"
OUTER = "executor.run"
MATMULS = ("convolution", "dot")
TOP_TYPES, TOP_OPS, TOP_GAPS, TOP_SCOPES = 15, 10, 5, 8

# one HLO instruction: its scope path ("" if none), the computation it
# calls (a fusion's, an async op's) and its operands' names
Instr = collections.namedtuple("Instr", "opcode scope calls operands")


def scope_path(op_name):
    """"forward/while/5/forward/mul/2" of an HLO op_name; "" if none."""
    return "/".join(SCOPE.findall(op_name or ""))


def _operands(body, opcode):
    """Names of an instruction's operands: the "%names" between
    "<opcode>(" and its closing parenthesis."""
    at = body.find(" %s(" % opcode)
    if at < 0:
        return ()
    start = at + len(opcode) + 2
    depth = 1
    for i in range(start, len(body)):
        depth += {"(": 1, ")": -1}.get(body[i], 0)
        if depth == 0:
            return tuple(re.findall(r"%[\w.\-]+", body[start:i]))
    return ()


def parse_hlo(texts, parse_op):
    """{instruction name: Instr} and {computation name: ([member names],
    root name)} of optimized HLO texts."""
    instrs, comps, current = {}, {}, None
    for text in texts:
        for line in text.splitlines():
            if not line.startswith(" "):
                head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(", line)
                current = None
                if head and line.rstrip().endswith("{"):
                    current = comps.setdefault(head.group(1), [[], None])
                continue
            body = line.strip()
            root = body.startswith("ROOT ")
            if root:
                body = body[5:]
            if " = " not in body or current is None:
                continue
            name, opcode, _ = parse_op(body)
            op_name = re.search(r'op_name="([^"]*)"', body)
            calls = re.search(r"\bcalls=(%[\w.\-]+)", body)
            instrs[name] = Instr(
                opcode, scope_path(op_name.group(1)) if op_name else "",
                calls.group(1) if calls else None, _operands(body, opcode))
            current[0].append(name)
            if root:
                current[1] = name
    return instrs, comps


def attribute(name, instrs, comps):
    """(scope path, {roles of the scopes fused into it}, {member scopes})
    of one executed instruction; ("", ...) when it cannot be found."""
    if name not in instrs:
        return "", set(), set()
    ins = instrs[name]
    members, root = comps.get(ins.calls, ([], None))
    member_scopes = [instrs[m].scope for m in members if instrs[m].scope]
    scope = next((instrs[m].scope for m in members
                  if instrs[m].opcode in MATMULS and instrs[m].scope), "")
    if not scope and root is not None:
        scope = instrs[root].scope
    scope = scope or ins.scope
    if not scope and member_scopes:
        scope = statistics.mode(member_scopes)
    if not scope and ins.opcode.endswith("-done") and ins.operands[:1] \
            and ins.operands[0] in instrs:
        scope = attribute(ins.operands[0], instrs, comps)[0]
    scopes = set(member_scopes) | ({scope} if scope else set())
    return scope, {s.split("/")[0] for s in scopes}, scopes


def consumers(names, instrs, comps):
    """{name: scope of the first instruction with a scope that reads its
    result, through at most three scopeless readers} for the instructions
    `names`: a copy the compiler put in has no Fluid op of its own, but
    it moves a buffer for one."""
    users = {}
    for user, ins in instrs.items():
        for o in ins.operands:
            users.setdefault(o, []).append(user)
    out = {}
    for name in names:
        frontier = [name]
        for _ in range(4):
            nxt = [u for n in frontier for u in users.get(n, ())]
            found = next(filter(None, (attribute(u, instrs, comps)[0]
                                       for u in nxt)), "")
            if found or not nxt:
                out[name] = found
                break
            frontier = nxt
    return out


def owned_time(ops):
    """ns of the busy union each op of [(start, end, is_collective)] owns:
    an instant belongs to the op that started last among those running
    then, a compute op before a collective."""
    order = sorted(range(len(ops)), key=lambda i: ops[i][0])
    times = sorted({t for s, e, _ in ops for t in (s, e)})
    owned, running, k = [0.0] * len(ops), [], 0
    for t0, t1 in zip(times, times[1:]):
        while k < len(order) and ops[order[k]][0] <= t0:
            i = order[k]
            heapq.heappush(running, (ops[i][2], -ops[i][0], i))
            k += 1
        while running and ops[running[0][2]][1] <= t0:
            heapq.heappop(running)
        if running:
            owned[running[0][2]] += t1 - t0
    return owned


def _is_collective(tr, name, opcode):
    return bool(tr.COLLECTIVE.match(name) or tr.COLLECTIVE.match(opcode))


def _merge_async_collectives(ops, tr):
    """A collective in flight from its -start to its -done is one
    interval: each "<collective>-done" event takes the start of the
    latest "<collective>-start" event it names as operand."""
    started, out = {}, []
    for s, e, text in sorted(ops):
        name, opcode, _ = tr.parse_op(text)
        if _is_collective(tr, name, opcode) and opcode.endswith("-start"):
            started[name] = len(out)
            out.append([s, e, text])
            continue
        if _is_collective(tr, name, opcode) and opcode.endswith("-done"):
            operand = re.search(r"-done\([^%]*(%[\w.\-]+)\)", text)
            at = started.pop(operand.group(1), None) if operand else None
            if at is not None:
                out[at][1] = e
                continue
        out.append([s, e, text])
    return [tuple(op) for op in out]


def _label_gap(lo, hi, spans):
    """The innermost span covering most of [lo, hi]: the shortest of those
    that cover at least half of it, else the one covering most, else
    "caller" (no Executor.run is under way: the loop, the read-back)."""
    covers = [(min(e, hi) - max(s, lo), e - s, name) for s, e, name in spans
              if min(e, hi) > max(s, lo)]
    if not covers:
        return "caller"
    half = [c for c in covers if c[0] >= 0.5 * (hi - lo)]
    if half:
        return min(half, key=lambda c: c[1])[2]
    return max(covers)[2]


def _gap_owners(lo, hi, spans):
    """ms of the gap [lo, hi] under each of the program's spans, by bare
    name (a nested span's time is also its parent's), and "caller": the
    part under no executor.run."""
    owners = {}
    for s, e, name in spans:
        cover = min(e, hi) - max(s, lo)
        if cover > 0:
            key = name[len(SPAN_PREFIX):]
            owners[key] = owners.get(key, 0.0) + cover * 1e-6
    owners["caller"] = (hi - lo) * 1e-6 - owners.get(OUTER, 0.0)
    return owners


def _calls(spans):
    """Per executor.run call: ms under each span name inside it, its own
    ms, and "other": its time under no child span."""
    outer = sorted(sp for sp in spans if sp[2] == SPAN_PREFIX + OUTER)
    calls = []
    for s, e, _ in outer:
        inside = [(s2, e2, n[len(SPAN_PREFIX):]) for s2, e2, n in spans
                  if s <= s2 and e2 <= e and n != SPAN_PREFIX + OUTER]
        call = {OUTER: (e - s) * 1e-6}
        for s2, e2, n in inside:
            call[n] = call.get(n, 0.0) + (e2 - s2) * 1e-6
        covered, at = 0.0, s
        for s2, e2 in sorted((s2, e2) for s2, e2, _ in inside):
            covered += max(0.0, e2 - max(s2, at))
            at = max(at, e2)
        call["other"] = (e - s - covered) * 1e-6
        calls.append(call)
    return calls


def reduce_profile(profile, texts, tr):
    """See the module docstring; `tr` is benchmark/trace_reduce.py.  None
    when the trace has no device plane with op events (a CPU run)."""
    devices, spans = {}, []
    for plane in profile.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            ops = tr._events(lines[tr.OPS_LINE]) if tr.OPS_LINE in lines else []
            if ops:
                devices[int(m.group(1))] = (
                    ops, tr._events(lines[tr.MODULES_LINE])
                    if tr.MODULES_LINE in lines else [])
        elif plane.name == tr.HOST_PLANE:
            for ln in plane.lines:
                spans += [ev for ev in tr._events(ln)
                          if ev[2].startswith(SPAN_PREFIX)]
    if not devices:
        return None
    ops, modules = devices[min(devices)]
    lo, hi, steps = tr._window(ops, modules)
    inside = _merge_async_collectives(
        [(max(s, lo), min(e, hi), n) for s, e, n in ops
         if min(e, hi) > max(s, lo)], tr)
    names = [tr.parse_op(n) for _, _, n in inside]
    owned = owned_time([(s, e, _is_collective(tr, name, opcode))
                        for (s, e, _), (name, opcode, _) in zip(inside, names)])
    busy_ns = sum(owned)
    instrs, comps = parse_hlo(texts, tr.parse_op)

    cache, scope_ns, by_op, mixed_ns, exposed_ns = {}, {}, {}, 0.0, 0.0
    for (name, opcode, kind), ns in zip(names, owned):
        if name not in cache:
            cache[name] = attribute(name, instrs, comps)
        scope, roles, scopes = cache[name]
        scope_ns[scope] = scope_ns.get(scope, 0.0) + ns
        entry = by_op.setdefault(name, [0.0, opcode, kind, scope, scopes])
        entry[0] += ns
        if len(roles) > 1:
            mixed_ns += ns
        if _is_collective(tr, name, opcode):
            exposed_ns += ns

    busy = tr._union([(s, e) for s, e, _ in inside])
    gaps, at = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > at:
            gaps.append((s - at, at, s))
        at = max(at, e)
    idle_ns = sum(g[0] for g in gaps)
    run_spans = tr._union([(s, e) for s, e, n in spans
                           if n == SPAN_PREFIX + OUTER])
    idle_in_run = sum(max(0.0, min(e, ge) - max(s, gs))
                      for _, gs, ge in gaps for s, e in run_spans)
    return {
        "steps": steps, "window_ms": (hi - lo) * 1e-6,
        "busy_ms": busy_ns * 1e-6,
        "slice_step_ms": (hi - lo) * 1e-6 / steps if steps else None,
        "scope_ms": {k: v * 1e-6 for k, v in scope_ns.items()},
        "exposed_collective_share": (100.0 * exposed_ns / busy_ns
                                     if busy_ns else None),
        "idle_in_executor_share": (100.0 * idle_in_run / idle_ns
                                   if idle_ns else None),
        "mixed_role_share": 100.0 * mixed_ns / busy_ns if busy_ns else None,
        "calls": _calls(spans),
        "device_ops": sorted(by_op.items(), key=lambda kv: -kv[1][0]),
        "idle_gaps": [[_label_gap(s, e, spans), d * 1e-6,
                       _gap_owners(s, e, spans)]
                      for d, s, e in sorted(gaps, reverse=True)[:TOP_GAPS]],
        "unjoined_ops": sum(1 for n in by_op if n not in instrs),
        "served": consumers([n for n, v in by_op.items() if not v[3]],
                            instrs, comps),
    }


def scope_ms(prof, match):
    """ms of busy time owned by the scope paths `match` (a regular
    expression, matched at the start of the path) fits; the path of an op
    with no scope is ""."""
    pat = re.compile(match)
    return sum(ms for path, ms in prof["scope_ms"].items() if pat.match(path))


def _summary(prof):
    """What the next perf_opt issue is written from: ms a step by Fluid op
    type within each role (outermost scope), the longest device ops each
    with the distinct "<role>/<type>" fused into it, the share of busy
    time in fusions that span roles, the longest idle gaps by owner, and
    the Executor's spans per call (medians)."""
    steps = prof["steps"] or 1
    by_type = {}
    for path, ms in prof["scope_ms"].items():
        key = "/".join(path.split("/")[:2]) if path else "unattributed"
        by_type[key] = by_type.get(key, 0.0) + ms

    def fused(scopes):
        kinds = {}
        for s in scopes:
            k = "/".join(s.split("/")[:2])
            kinds[k] = kinds.get(k, 0) + 1
        top = sorted(kinds.items(), key=lambda kv: (-kv[1], kv[0]))
        return (["%s x%d" % kv for kv in top[:TOP_SCOPES]]
                + (["+%d more" % (len(top) - TOP_SCOPES)]
                   if len(top) > TOP_SCOPES else []))

    blind = {}  # unattributed time by opcode and by the op it serves
    for name, (ns, opcode, _, scope, _) in prof["device_ops"]:
        if not scope:
            served = "/".join(prof["served"].get(name, "").split("/")[:2])
            key = "%s for %s" % (opcode, served or "?")
            blind[key] = blind.get(key, 0.0) + ns * 1e-6
    span_names = sorted({n for c in prof["calls"] for n in c})
    return {
        "steps": prof["steps"], "slice_step_ms": prof["slice_step_ms"],
        "busy_ms_per_step": prof["busy_ms"] / steps,
        "role_share": {r: 100.0 * scope_ms(prof, m) / prof["busy_ms"]
                       for r, m in (("forward", "forward/"),
                                    ("backward", "backward/"),
                                    ("optimize", "(optimize|lrsched)/"),
                                    ("unattributed", "$"))},
        "op_types_ms_per_step": [
            [k, ms / steps, 100.0 * ms / prof["busy_ms"]] for k, ms in
            sorted(by_type.items(), key=lambda kv: -kv[1])[:TOP_TYPES]],
        "device_ops_ms_per_step": [
            [" ".join(x for x in (name, opcode, kind) if x), ns * 1e-6 / steps,
             scope or "unattributed", fused(scopes)]
            for name, (ns, opcode, kind, scope, scopes)
            in prof["device_ops"][:TOP_OPS]],
        "unattributed_ms_per_step": [
            [k, ms / steps] for k, ms in
            sorted(blind.items(), key=lambda kv: -kv[1])[:TOP_OPS]],
        "mixed_role_share": prof["mixed_role_share"],
        "exposed_collective_share": prof["exposed_collective_share"],
        "idle_in_executor_share": prof["idle_in_executor_share"],
        "idle_gaps_ms": prof["idle_gaps"],
        "span_ms_per_call": {n: statistics.median(c.get(n, 0.0)
                                                  for c in prof["calls"])
                             for n in span_names},
        "unjoined_ops": prof["unjoined_ops"],
    }


def _slice(ctx, step_record):
    """Trace work["trace_steps"] steady steps of the measured executable,
    driven as loops/train.py drives the window; (directory to remove,
    .xplane.pb path or None)."""
    import jax
    import numpy as np

    import paddle_tpu as fluid

    exe, main = ctx["exe"], ctx["main"]
    batch = ctx["adapter"].make_batch(ctx["cfg"], ctx["work"],
                                      ctx["args"].seed * 1000)
    out_dir = os.path.join(ctx["root"], ".bench_trace",
                           ctx["cell"]["name"] + ".program_profile")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    compiles = exe.compile_count
    every = int(ctx["work"].get("readback_every", 0))

    def step():
        return exe.run(main, feed=batch, fetch_list=step_record.fetches,
                       return_numpy=False)

    # without the Python tracer: it hooks every Python call and makes the
    # Executor's loops over the state variables ~5x slower than they run
    # untraced (an `executor.run` of 5.0 ms read 6.7 ms under it); the
    # program's spans are TraceMe events and stay
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with fluid.scope_guard(ctx["scope"]):
        jax.block_until_ready(step())
        jax.profiler.start_trace(out_dir, profiler_options=options)
        try:
            for i in range(int(ctx["work"]["trace_steps"])):
                out = step()
                if every and out and (i + 1) % every == 0:
                    np.asarray(out[0])  # the loop's read-back
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
    if exe.compile_count != compiles:
        raise AssertionError(
            "program_profile: the slice compiled (%d -> %d): it did not run "
            "the executable the window ran"
            % (compiles, exe.compile_count))
    files = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return out_dir, files[0] if files else None


def _profile(ctx):
    from jax.profiler import ProfileData

    exe, main = ctx.get("exe"), ctx.get("main")
    steps_of = getattr(exe, "compiled_steps", None)
    if steps_of is None or main is None:
        return None  # a program from before the scopes and spans
    records = steps_of(main)
    if not records:
        return None
    tr = ctx["load_module"]("", "trace_reduce")
    out_dir, path = _slice(ctx, records[-1])
    try:
        if path is None:
            return None
        keep = ctx["args"].keep_trace
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(
                keep, ctx["cell"]["name"] + ".program_profile.xplane.pb"))
        data = ProfileData.from_file(path)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    if not any(tr.DEVICE_PLANE.match(p.name) for p in data.planes):
        return None
    texts = ctx["load_module"]("readers", "hlo_text").texts(ctx) or []
    prof = reduce_profile(data, texts, tr)
    if prof is not None:
        ctx["log"]("program_profile: " + json.dumps(_summary(prof)))
    return prof


def profile(ctx):
    """The traced run's profile, taken once and kept on ctx (a failure
    too: one broken slice must not run again for every metric)."""
    if "program_profile" not in ctx:
        ctx["program_profile"] = None
        ctx["program_profile"] = _profile(ctx)
    return ctx["program_profile"]


def read(ctx, key):
    """A number the profile carries under `key`."""
    prof = profile(ctx)
    return None if prof is None else prof.get(key)
