"""The serving engine's own spans, from the host plane of the traced
slice: `paddle_tpu:serve_admit`, `paddle_tpu:serve_step` (the pooled
dispatch and the fetch of its logits) and `paddle_tpu:serve_sample`
(RecordEvents of paddle_tpu/serving/engine.py), inside the loop's
`bench:serve_iter` span around each `engine.step()`.  All on the profiler's
clock, with the device ops.

reduce(profile) -> {
  "iterations"   engine steps wholly inside the trace
  "iter_ms"      median wall ms of one engine step
  "run_share", "sample_share", "admit_share"
                 % of the iterations' wall time under each span
  "other_share"  % under none of them: the scheduler's Python, the feed
                 built, the results kept
} or None where the trace holds no iteration.  read(ctx, key) hands one of
them out (the loop keeps the reduction in ctx["serve_spans"]).
"""

import statistics

ITER = "bench:serve_iter"
SPANS = {"run_share": "paddle_tpu:serve_step",
         "sample_share": "paddle_tpu:serve_sample",
         "admit_share": "paddle_tpu:serve_admit"}
HOST_PLANE = "/host:CPU"


def reduce_events(events, unit_ms=1.0):
    """events: [(start, end, name)] in one unit of time, `unit_ms` ms long."""
    iters = sorted((s, e) for s, e, n in events if n == ITER)
    if not iters:
        return None
    total = sum(e - s for s, e in iters)
    out = {"iterations": len(iters), "other_share": 100.0,
           "iter_ms": unit_ms * statistics.median(e - s for s, e in iters)}
    for key, name in SPANS.items():
        under = 0.0
        for s, e, n in events:
            if n != name:
                continue
            # a span counts where it lies inside an iteration
            under += sum(max(0.0, min(e, ie) - max(s, i0))
                         for i0, ie in iters if i0 < e and s < ie)
        out[key] = 100.0 * under / total
        out["other_share"] -= out[key]
    return out


def reduce(profile):
    events = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            events += [(float(ev.start_ns), float(ev.start_ns)
                        + float(ev.duration_ns), ev.name)
                       for ev in line.events
                       if ev.name == ITER or ev.name in SPANS.values()]
    return reduce_events(events, unit_ms=1e-6)   # the trace is in ns


def read(ctx, key):
    spans = ctx.get("serve_spans")
    return None if not spans else spans.get(key)
