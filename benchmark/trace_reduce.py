"""From a profiler trace (.xplane.pb) to the benchmark's device numbers.

Built on jax.profiler.ProfileData alone.  What it reads:

  device planes   "/device:TPU:<i>"; line "XLA Ops" holds one event per
                  executed HLO op (start, duration), line "XLA Modules" one
                  per executed program.
  host plane      "/host:CPU"; the benchmark's own TraceAnnotation spans
                  (names starting with "bench:") on any thread line.

What it gives, per trace (see reduce_profile):

  window_s        the steady window: from the start of the SECOND run of the
                  device's main program (the one with most time) to the end of
                  its last run, so the bubble that starting the profiler makes
                  is left out; averaged over the devices used.
  busy_s          union of the op intervals inside the window (averaged).
  idle_share      1 - busy_s / window_s.
  steps           runs of the main program inside the window (device 0).
  device_ops      [[label, seconds], ...] most time first, device 0; the
                  label is the instruction's name, opcode, fusion kind and the
                  start of its result type (the trace names an op by its whole
                  HLO text).
  op_categories   [[opcode + fusion kind, seconds], ...] the same time summed
                  by kind of op, device 0.
  idle_gaps       [[label, seconds], ...] the longest idle gaps on device 0,
                  each labelled with the bench: span that covers most of it.
  collective_share  time in collective ops on device 0 over its busy time.

Checked against benchmark/tests/data/ by benchmark/tests/test_trace_reduce.py.
"""

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")


def parse_op(name):
    """(instruction name, opcode, fusion kind or "") of an "XLA Ops" event,
    whose name is the whole HLO instruction:
    "%fusion.3 = (f32[8]{0}, bf16[8,4]{1,0}) fusion(f32[8]{0} %p), kind=kLoop, calls=..."."""
    lhs, sep, rest = name.partition(" = ")
    if not sep:
        return name, name.lstrip("%").split(".")[0], ""
    depth, opcode = 0, ""
    for i, ch in enumerate(rest):
        if ch in "([{":
            if ch == "(" and depth == 0:
                word = re.search(r"([A-Za-z_][\w-]*)$", rest[:i])
                if word:
                    opcode = word.group(1)
                    break
            depth += 1
        elif ch in ")]}":
            depth -= 1
    kind = re.search(r"\bkind=(k\w+)", rest)
    return lhs, opcode or "?", kind.group(1) if kind else ""


def label(name, width=72):
    """A one-line label for the breakdown: instruction name, opcode, fusion
    kind and the start of the result type."""
    lhs, opcode, kind = parse_op(name)
    _, sep, rest = name.partition(" = ")
    result = rest[:rest.find(" " + opcode + "(")] if sep and opcode else ""
    if len(result) > width:
        result = result[:width] + "..."
    return " ".join(x for x in (lhs, opcode, kind, result) if x)


def category(name):
    _, opcode, kind = parse_op(name)
    return (opcode + " " + kind).strip()


def _events(line):
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name) for e in line.events]


def _union(intervals):
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _total(intervals):
    return sum(e - s for s, e in intervals)


def _window(ops, modules):
    """(lo, hi, steps) of the steady window on one device."""
    if modules:
        by_name = {}
        for s, e, name in modules:
            by_name.setdefault(name, []).append((s, e))
        runs = sorted(max(by_name.values(), key=_total))
        if len(runs) >= 3:
            runs = runs[1:]
        return runs[0][0], runs[-1][1], len(runs)
    return min(s for s, _, _ in ops), max(e for _, e, _ in ops), None


def _label_gap(lo, hi, spans):
    """The host span that covers most of [lo, hi], else "other"."""
    best, best_cover = "other", 0.0
    for s, e, name in spans:
        cover = min(e, hi) - max(s, lo)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def _reduce_device(ops, modules, spans):
    lo, hi, steps = _window(ops, modules)
    inside = [(max(s, lo), min(e, hi), n) for s, e, n in ops
              if min(e, hi) > max(s, lo)]
    busy = _union([(s, e) for s, e, _ in inside])
    by_op, by_category = {}, {}
    for s, e, n in inside:
        by_op[n] = by_op.get(n, 0.0) + (e - s)
        c = category(n)
        by_category[c] = by_category.get(c, 0.0) + (e - s)
    gaps, at = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > at:
            gaps.append((s - at, at, s))
        at = max(at, e)
    gaps.sort(reverse=True)
    coll = _union([(s, e) for s, e, n in inside if COLLECTIVE.match(n)])
    busy_ns = _total(busy)
    return {
        "window_s": (hi - lo) * 1e-9, "busy_s": busy_ns * 1e-9,
        "steps": steps,
        "device_ops": [[label(n), t * 1e-9] for n, t in sorted(
            by_op.items(), key=lambda kv: -kv[1])],
        "op_categories": [[c, t * 1e-9] for c, t in sorted(
            by_category.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[_label_gap(s, e, spans), d * 1e-9]
                      for d, s, e in gaps],
        "collective_share": _total(coll) / busy_ns if busy_ns else 0.0,
    }


SPAN_PREFIX = "bench:"  # the benchmark's own host spans
TOP_OPS, TOP_GAPS = 10, 5


def reduce_profile(profile, n_devices=1):
    """See the module docstring.  None when the trace has no device plane
    with op events (a CPU run)."""
    devices, spans = {}, []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                ops = _events(lines[OPS_LINE])
                if ops:
                    devices[int(m.group(1))] = (
                        ops, _events(lines[MODULES_LINE])
                        if MODULES_LINE in lines else [])
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                spans += [ev for ev in _events(ln)
                          if ev[2].startswith(SPAN_PREFIX)]
    if not devices:
        return None
    used = sorted(devices)[:n_devices]
    per = [_reduce_device(devices[i][0], devices[i][1], spans) for i in used]
    first = per[0]
    busy_s = sum(d["busy_s"] for d in per) / len(per)
    window_s = sum(d["window_s"] for d in per) / len(per)
    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s else None,
        "steps": first["steps"],
        "device_ops": first["device_ops"][:TOP_OPS],
        "op_categories": first["op_categories"][:TOP_OPS],
        "idle_gaps": first["idle_gaps"][:TOP_GAPS],
        "collective_share": first["collective_share"],
        "devices": used,
        "per_device": [{"busy_s": d["busy_s"], "window_s": d["window_s"]}
                       for d in per],
    }


def reduce_file(path, **kw):
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), **kw)


def dump(path, n=5):
    """Planes, lines and a few events of each: look at a trace by hand
    before trusting code against it."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for ln in plane.lines:
            evs = list(ln.events)
            print("  line %r: %d events" % (ln.name, len(evs)))
            for e in evs[:n]:
                print("    %-60s start %.0f ns, %.0f ns"
                      % (e.name[:60], e.start_ns, e.duration_ns))


if __name__ == "__main__":
    import json
    import sys

    if sys.argv[1] == "--dump":
        dump(sys.argv[2])
    else:
        print(json.dumps(reduce_file(sys.argv[1], n_devices=int(
            sys.argv[2]) if len(sys.argv) > 2 else 1), indent=1))
