"""The training loop every `"kind": "train"` cell is measured with.

Fluid's own contract, nothing staged: a ring of distinct host-numpy
batches made from --seed during set-up, one handed per step to
`exe.run(main, feed=batch, fetch_list=[loss], return_numpy=False)`, so the
feed upload is inside the measurement.  Steps are dispatched without
blocking; the loss is read back every `readback_every`-th step, as a loop
that logs does, and after the last one.  The window opens when warm-up has
been blocked on and closes when the last dispatched step is ready.

The pace is the time per step over the intervals between consecutive
read-backs in the window, leaving out the intervals that held a stall:
those whose time per step is more than 2% over the median interval's.
Each interval holds `readback_every` steps and one drain of the pipeline,
so feed upload and dispatch stay inside it.  Throughput and MFU are work
per step over that pace.  The host of a one-chip machine is shared: one
stall of 0.1-2 s in a window (seen in one run of about five, PERF.md
section 6) moves work / window by 0.3-11%.  What is left out is reported
beside it as `stall_share`.  (The median itself would do for three of
the cells; ResNet-50's intervals come in two modes 0.9% apart whose
shares move from 14 to 54% between runs, and a median jumps between them.)

Flags are whatever the program defaults to: the loop sets none.

run(ctx) fills ctx with what the per-layer readers read and returns
{correct, attempted, failed, metrics, memory_peak_bytes, detail}.
"""

import glob
import math
import os
import shutil
import statistics
import time


def _scalar(x):
    import numpy as np

    return float(np.asarray(x).reshape(-1)[0])


def _params_in_order(program, scope):
    """(name, value) of the program's parameters in creation order — the
    order the architecture creates them in, which is how the plain
    reference finds its weights without knowing generated names."""
    return [(p.name, scope.find_var(p.name))
            for p in program.global_block().all_parameters()]


def _reference_check(ctx, fluid, exe, scope, mesh, batch0):
    """Program's dropout-free forward loss against the adapter's plain
    float32 reference, same weights (as they stand in the scope), on the
    first `reference_rows` rows of the first batch."""
    adapter, cfg, work = ctx["adapter"], ctx["cfg"], ctx["work"]
    sample = {k: v[:int(work["reference_rows"])] for k, v in batch0.items()}
    fwd = adapter.build(cfg, work, mesh=mesh, forward_only=True)
    with fluid.scope_guard(scope):
        got = _scalar(exe.run(fwd["main"], feed=sample,
                              fetch_list=[fwd["loss"]])[0])
    params = _params_in_order(fwd["main"], scope)
    ref = float(adapter.reference_loss(cfg, params, sample))
    # at a rehearsal's tiny widths bf16 rounding averages over far fewer
    # elements, so its data may carry a tolerance of its own; a measured
    # run is held to the adapter's
    tolerance = (cfg.get("reference_tolerance", adapter.TOLERANCE)
                 if ctx["rehearse"] else adapter.TOLERANCE)
    return {"program_loss": got, "reference_loss": ref,
            "abs_diff": abs(got - ref), "tolerance": tolerance}


def _trace_slice(ctx, step, n_steps, sync):
    """A profiler trace of n_steps steady steps, reduced by
    benchmark/trace_reduce.py; None where the trace has no device plane
    (a rehearsal on the CPU)."""
    import jax

    trace_reduce = ctx["load_module"]("", "trace_reduce")
    out_dir = os.path.join(ctx["root"], ".bench_trace", ctx["cell"]["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    sync()
    jax.profiler.start_trace(out_dir)
    try:
        for _ in range(n_steps):
            step()
        sync()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    reduced = None
    if files:
        keep = ctx["args"].keep_trace
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(files[0], os.path.join(
                keep, ctx["cell"]["name"] + ".xplane.pb"))
        reduced = trace_reduce.reduce_file(files[0], n_devices=ctx["chips"])
    shutil.rmtree(out_dir, ignore_errors=True)
    return reduced


# an interval whose time per step is this far over the median interval's
# held a stall; ResNet-50's two modes are 0.9% apart and stay in
STALL_OVER_MEDIAN = 1.02


def pace(marks, attempted, window_s):
    """(seconds per step, stall share in %, intervals) of a window from
    its read-back marks [(steps dispatched, host clock)]: the time per
    step over the intervals between consecutive marks that held no stall,
    and the share of all intervals' time that the stalled ones spent over
    that pace.  A window too short to hold two read-backs is one
    interval."""
    intervals = [(n1 - n0, t1 - t0)
                 for (n0, t0), (n1, t1) in zip(marks, marks[1:])]
    if not intervals:
        intervals = [(attempted, window_s)]
    limit = STALL_OVER_MEDIAN * statistics.median(t / n for n, t in intervals)
    kept = [(n, t) for n, t in intervals if t / n <= limit]
    step_s = sum(t for _, t in kept) / sum(n for n, _ in kept)
    total_s = sum(t for _, t in intervals)
    stall_s = total_s - step_s * sum(n for n, _ in intervals)
    return step_s, 100.0 * stall_s / total_s, len(intervals)


def run(ctx):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.parallel.mesh import make_mesh

    adapter, cfg, work, log = (ctx["adapter"], ctx["cfg"], ctx["work"],
                               ctx["log"])
    seed, seconds = ctx["args"].seed, ctx["seconds"]
    annotate = jax.profiler.TraceAnnotation

    mesh = (make_mesh(dict(work["mesh"]), ctx["devices"])
            if work.get("mesh") else None)
    built = adapter.build(cfg, work, mesh=mesh, forward_only=False)
    main, startup, loss_var = built["main"], built["startup"], built["loss"]
    # the seed reaches the program only as random_seed
    startup.random_seed = main.random_seed = seed + 1
    place = fluid.CPUPlace() if ctx["rehearse"] else fluid.TPUPlace(0)
    scope = fluid.Scope()
    ring = [adapter.make_batch(cfg, work, seed * 1000 + i)
            for i in range(int(work["ring"]))]
    work_per_batch = [float(adapter.work_units(b)) for b in ring]
    flops_per_step = float(adapter.model_flops(cfg, work))

    exe = fluid.Executor(place)
    losses, run_call_s = [], []  # one loss (None: the call raised) per step
    readbacks = []  # (steps dispatched, host clock) after each read-back
    every = int(work["readback_every"])

    def step():
        i = len(losses)
        with annotate("bench:feed"):
            batch = ring[i % len(ring)]
        t = time.perf_counter()
        try:
            with annotate("bench:run"):
                out = exe.run(main, feed=batch, fetch_list=[loss_var],
                              return_numpy=False)
        except Exception as e:
            losses.append(None)
            log("step %d raised: %r" % (i, e))
            if losses.count(None) > 3:
                raise
            return
        run_call_s.append(time.perf_counter() - t)
        losses.append(out[0])
        if (i + 1) % every == 0:
            with annotate("bench:readback"):
                _scalar(out[0])
            readbacks.append((i + 1, time.perf_counter()))

    def sync():
        live = [x for x in losses if x is not None]
        if live:
            with annotate("bench:readback"):
                jax.block_until_ready(live[-1])

    with fluid.scope_guard(scope):
        t_startup = time.perf_counter()
        exe.run(startup)
        jax.block_until_ready([v for _, v in _params_in_order(main, scope)
                               if v is not None][-1:])
        t_started = time.perf_counter()
        for _ in range(int(work["warmup_steps"])):
            step()
            sync()
            if len(losses) == 1:
                first_step_s = time.perf_counter() - t_started
        setup_s = time.perf_counter() - ctx["t_start"]
        log("set-up %.2f s (startup %.2f s, first step %.2f s)"
            % (setup_s, t_started - t_startup, first_step_s))

        # ---- the measured window ----
        n_warm = len(losses)
        compiles0, feed_ms0 = exe.compile_count, exe.host_feed_ms
        del run_call_s[:]
        t0 = time.perf_counter()
        # a rehearsal's data may cap the steps, so that what it trains and
        # checks does not depend on how fast this host happens to be
        max_steps = work.get("max_steps")
        while (time.perf_counter() - t0 < seconds
               and (max_steps is None or len(losses) - n_warm < max_steps)):
            step()
        sync()
        window_s = time.perf_counter() - t0
        # ---- closed ----
        attempted = len(losses) - n_warm
        compiles_in_window = exe.compile_count - compiles0
        host_feed_ms = (exe.host_feed_ms - feed_ms0) / max(1, attempted)
        run_call_ms = 1e3 * statistics.median(run_call_s)
        # buffers (state, feeds) and the region the runtime reserves for
        # the programs' temporaries are counted apart on this runtime;
        # a train step's memory is mostly the second
        memory_stats = [d.memory_stats() or {} for d in ctx["devices"]]
        peak_bytes = max(int(st.get("peak_bytes_in_use", 0))
                         + int(st.get("peak_bytes_reserved", 0))
                         for st in memory_stats)

        loss_np = [float("nan") if x is None else _scalar(x) for x in losses]
        window_losses = loss_np[n_warm:]
        done = [i for i, v in enumerate(window_losses) if math.isfinite(v)]
        bad = attempted - len(done)
        work_done = sum(work_per_batch[(n_warm + i) % len(ring)]
                        for i in done)
        marks = [(n - n_warm, t - t0) for n, t in readbacks
                 if t0 < t <= t0 + window_s]
        step_s, stall_share, n_intervals = pace(marks, attempted, window_s)
        # a failed step counts as dispatched and as no work
        rate = work_done / attempted / step_s / ctx["chips"]
        mfu = (100.0 * flops_per_step * len(done) / attempted / step_s
               / (ctx["chips"] * ctx["peak"]["flops_per_s"]))

        n = len(ring)
        first_pass, last_pass = loss_np[:n], loss_np[-n:]
        falls = (len(loss_np) >= 2 * n
                 and statistics.fmean(last_pass) < statistics.fmean(first_pass))

        trace = None
        if ctx["args"].trace:
            trace = _trace_slice(ctx, step, int(work["trace_steps"]), sync)
        ref = _reference_check(ctx, fluid, exe, scope, mesh, ring[0])

    ref_ok = ref["abs_diff"] <= ref["tolerance"]
    correct = compiles_in_window == 0 and bad == 0 and falls and ref_ok
    ctx.update({
        "exe": exe, "main": main, "scope": scope, "trace": trace,
        "flops_per_step": flops_per_step,
        "counters": {
            "run_call_ms": run_call_ms,
            "compiles_in_window": compiles_in_window,
            "host_feed_ms": host_feed_ms,
            "first_step_s": first_step_s,
            "stall_share": stall_share,
            "peak_hbm_gib": peak_bytes / 2.0 ** 30 if peak_bytes else None,
        },
    })
    metrics = {cfg["throughput_metric"]: rate, "train_mfu": mfu,
               "setup_s": setup_s}
    detail = {
        "steps": attempted, "window_s": window_s,
        "step_ms": 1e3 * step_s,
        "window_step_ms": 1e3 * window_s / max(1, attempted),
        "intervals": n_intervals, "stall_share": stall_share,
        "flops_per_step": flops_per_step,
        "loss_first_pass": statistics.fmean(first_pass),
        "loss_last_pass": statistics.fmean(last_pass),
        "loss_falls": falls, "compiles_in_window": compiles_in_window,
        "reference": ref, "reference_ok": ref_ok,
        "startup_s": t_started - t_startup, "first_step_s": first_step_s,
        "host_feed_ms": host_feed_ms, "metrics": metrics,
        "memory_stats": memory_stats,
        "op_categories": trace["op_categories"] if trace else None,
        "readback_t": marks,
    }
    return {"correct": correct, "attempted": attempted,
            "failed": bad, "metrics": metrics,
            "memory_peak_bytes": peak_bytes, "detail": detail}
