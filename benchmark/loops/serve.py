"""The serving loop every `"kind": "serve"` cell is measured with
(protocol and reasons: benchmark/SERVING.md).

One process drives paddle_tpu.serving.ServingEngine open-loop on a clock:
a request is submitted when the clock passes its due time, whether or not
earlier ones have finished; `engine.step()` is called while anything is
queued or active, and the loop sleeps to the next due time otherwise.  The
engine keeps its own clock (steps); the loop keeps the seconds.  Every
request is timed FROM ITS DUE TIME, so a generator that runs late lengthens
what it measures and never hides it; how late it ran is reported.

    0 .. ramp_seconds                 arrivals at the cell's rate, not
                                      measured: the pool reaches its steady
                                      occupancy
    ramp .. ramp + --seconds          the window: `attempted` = requests due
                                      in it; tokens emitted in it count
    .. + drain_seconds                no arrivals; what is in flight is
                                      served to its end, or `failed`

The traffic is a data file (the workload's "traffic" group) read by ONE
generator, make_schedule(): every seed gets the SAME multiset of arrival
gaps, prompt lengths, output lengths and sampling parameters (the
quantiles of the stated distributions, as many as the rate and the span
ask for) in another order and another pairing, dealt in strata (_dealt),
so the work offered in a window, and in every few requests of it, does not
change with the seed.

`correct` compares LOGITS: the loop hands the engine its Executor through
a Tap, which keeps, for a sample of greedy requests drawn from the seed
before the window, the row of logits each of their tokens was picked from,
as the timed step fetched it; after the window the adapter's plain float32
reference runs once over each of those prompts with its served tokens and
the rows are compared element by element (compare()).

The clock and the sleep are taken from ctx ("clock", "sleep"; default the
wall clock), so that a test drives the loop on a fake clock and a
rehearsal serves the same thing on any host.

run(ctx) fills ctx for the readers and returns
{correct, attempted, failed, metrics, memory_peak_bytes, detail}.
"""

import glob
import json
import math
import os
import shutil
import statistics
import sys
import time

PREFILL = "prefill"  # paddle_tpu.serving.pool's state names, as it spells
                     # them: the loop reads slot states, never writes them


# --------------------------------------------------------------------------
# traffic: one general generator over a data file
# --------------------------------------------------------------------------
def _dealt(values, rng, stratum=6):
    """`values` (sorted) in an order the seed chooses, STRATIFIED: cut into
    `stratum` bands of neighbours, every run of `stratum` consecutive places
    gets one value of each band (which one, and where in the run, the seed
    says).  So every few requests of a schedule offer about the same work,
    and which long request falls at a window's edge moves a 20 s window
    less than under a plain shuffle (one order repeated moves
    tokens/s 0.4 %, plain shuffles 9 %: PERF.md section 2)."""
    n = len(values)
    runs = -(-n // stratum)
    out = [[] for _ in range(runs)]
    for j in range(stratum):
        band = values[j * runs:(j + 1) * runs]
        for k, run in enumerate(rng.permutation(runs)[:len(band)]):
            out[run].append(band[k])
    for run in out:
        rng.shuffle(run)
    return [x for run in out for x in run]


def _exp_gaps(n, total, rng):
    """n gaps summing to `total`: the n quantiles of an exponential
    distribution, dealt by the seed."""
    q = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = total / sum(q)
    return _dealt([scale * x for x in q], rng)


def _lengths(n, spec, rng):
    """n lengths: the quantiles of a log-normal (median, sigma) clipped to
    lo..hi, dealt by the seed."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        x = spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(spec["hi"], max(spec["lo"], round(x)))))
    return _dealt(out, rng)


def _arrivals(traffic, start, span, rng):
    """Due times in [start, start + span) at the mix's mean rate: Poisson
    arrivals, as many as the rate asks for."""
    n = max(1, int(round(float(traffic["arrivals"]["rate_rps"]) * span)))
    out, at = [], start
    for g in _exp_gaps(n, span, rng):
        out.append(at + 0.5 * g)   # due in the middle of its gap: none on
        at += g                    # the boundary
    return out


def _sampling(n, traffic, rng):
    """n sets of sampling parameters: a fixed share greedy (seed None), the
    rest with a temperature, top-k and top-p of their own from the file's
    lists, dealt round, then dealt by the seed."""
    spec = traffic["sampling"]
    n_sampled = int(round(n * float(spec["sampled_fraction"])))
    out = []
    for i in range(n):
        if i < n_sampled:
            out.append({
                "temperature": spec["temperature"][i % len(spec["temperature"])],
                "top_k": spec["top_k"][i % len(spec["top_k"])],
                "top_p": spec["top_p"][i % len(spec["top_p"])],
                "seed": 0})   # its own seed: make_schedule, from the content
        else:
            out.append({"temperature": 1.0, "top_k": 0, "top_p": 1.0,
                        "seed": None})
    return _dealt(out, rng)


def make_schedule(traffic, vocab, seed, segments):
    """The requests of one run, due order, all from `seed`.  `segments` =
    [(phase, start, span)]: each phase draws its own quantile sets, so the
    window's work is the same whatever the ramp's length.  A request:
    {rid, phase, due, prompt, max_new_tokens, temperature, top_k, top_p,
    seed}."""
    import numpy as np

    rng = np.random.default_rng(int(seed))
    out = []
    for phase, start, span in segments:
        due = _arrivals(traffic, start, span, rng)
        n = len(due)
        prompts = _lengths(n, traffic["prompt_len"], rng)
        outputs = _lengths(n, traffic["output_len"], rng)
        for t, p, o, s in zip(due, prompts, outputs,
                              _sampling(n, traffic, rng)):
            # p(k) ~ 1/k over the vocabulary, as the train cells draw theirs
            ids = np.floor(np.exp(rng.uniform(0.0, np.log(vocab), p))).astype(
                "int64").clip(1, vocab - 1)
            if s["seed"] is not None:
                s = dict(s, seed=int(rng.integers(0, 2 ** 31 - 1)))
            out.append(dict(s, phase=phase, due=t, prompt=ids,
                            max_new_tokens=o))
    out.sort(key=lambda r: r["due"])
    for i, r in enumerate(out):
        r["rid"] = i
    return out


# --------------------------------------------------------------------------
# clocks
# --------------------------------------------------------------------------
class FakeClock:
    """A clock that moves only when told: `step_s` for every engine step,
    and by what a sleep asks for.  What a rehearsal or a test serves then
    does not depend on the host's speed."""

    def __init__(self, step_s):
        self.now, self.step_s = 0.0, float(step_s)

    def __call__(self):
        return self.now

    def sleep(self, dt):
        self.now += max(0.0, dt)

    def step_done(self):
        self.now += self.step_s


class Tap:
    """The Executor the engine is handed: every call goes through to the
    real one unchanged; of each run of `program` (the engine's step) it
    remembers the feed's width_rows and the fetched logits [B, W, V], the
    very array the engine samples from.  keep() copies, for a watched
    request that the step just emitted a token for, the row that token was
    picked from: a slot's last real column (build_feed's sample plan)."""

    def __init__(self, exe):
        self._exe = exe
        self.program, self.watch, self.rows, self.last = None, set(), {}, None

    def __getattr__(self, name):
        return getattr(self._exe, name)

    def run(self, program=None, feed=None, **kw):
        out = self._exe.run(program, feed=feed, **kw)
        if program is self.program and self.watch:
            self.last = (feed["width_rows"], out[0])
        return out

    def keep(self, rid, slot):
        width_rows, logits = self.last
        self.rows.setdefault(rid, []).append(
            logits[slot, int(width_rows[slot]) - 1].copy())


# --------------------------------------------------------------------------
# the open loop
# --------------------------------------------------------------------------
def drive(engine, schedule, clock, sleep, t_end, make_request,
          step=None, step_done=None, on_time=(), tap=None):
    """Serve `schedule` open-loop until everything due is finished or the
    clock passes `t_end` (seconds from the loop's start).  `on_time` =
    [(t, callback)]: each callback runs once, before the first step that
    starts at or after t.  `tap` (a Tap) is told of every token a request
    it watches emits.  Returns (per-request records by rid, per-step
    records); times are seconds from the loop's start."""
    t0 = clock()
    reqs = {r["rid"]: dict(r, submitted=None, admitted=None, emitted=[],
                           status=None, tokens=None) for r in schedule}
    order = [r["rid"] for r in schedule]
    on_time = sorted(on_time, key=lambda x: x[0])
    step = step or engine.step
    steps, nxt, pos_before = [], 0, {}
    while True:
        now = clock() - t0
        while on_time and on_time[0][0] <= now:
            on_time.pop(0)[1]()
        while nxt < len(order) and reqs[order[nxt]]["due"] <= now:
            r = reqs[order[nxt]]
            engine.submit(make_request(r, engine.now))
            r["submitted"] = now
            nxt += 1
        active = engine.pool.active_slots()
        if not engine.queue and not active:
            if nxt >= len(order):
                break
            wake = reqs[order[nxt]]["due"]
            if on_time:
                wake = min(wake, on_time[0][0])
            if wake > t_end:
                break
            # never a sleep of nothing: a clock that rounds would not move
            sleep(max(1e-4, wake - now))
            continue
        if now >= t_end:
            break
        pos_before = {s.req.rid: (s.state, s.pos) for _, s in active}
        slot_of = {s.req.rid: slot for slot, s in active}
        terminal = step()
        if step_done is not None:
            step_done()
        after = clock() - t0
        rec = {"t0": now, "t1": after, "active": 0, "prefill_cols": 0,
               "decode_cols": 0, "context_sum": 0, "rows_read": 0,
               "sampled": 0, "live_rows": 0, "queued": len(engine.queue)}
        seen = {}
        for slot, s in engine.pool.active_slots():
            seen[s.req.rid] = (len(s.out), s.pos, s.req.prompt.size)
            slot_of[s.req.rid] = slot   # admitted inside this step
            rec["live_rows"] += s.pos
        for rid in terminal:
            res = engine.wire_results([rid])[0]
            r = reqs[rid]
            r["status"], r["tokens"] = res["status"], res["tokens"]
            if rid in pos_before or res["admit_step"] is not None:
                seen[rid] = (len(res["tokens"]), None, r["prompt"].size)
        for rid, (n_out, pos, p_len) in seen.items():
            r = reqs[rid]
            if r["admitted"] is None:
                r["admitted"] = now   # admitted inside the step that began at `now`
            state, before = pos_before.get(rid, (PREFILL, 0))
            cols = (min(engine.width, p_len - before) if state == PREFILL
                    else 1)
            rec["active"] += 1
            rec["prefill_cols" if state == PREFILL else "decode_cols"] += cols
            # column j of this step sits at position before + j and sees
            # the keys 0 .. before + j
            rec["context_sum"] += cols * before + cols * (cols + 1) // 2
            rec["rows_read"] += before + cols
            new = n_out - len(r["emitted"])
            rec["sampled"] += new
            r["emitted"] += [after] * new
            if new and tap is not None and rid in tap.watch:
                if new > 1:
                    raise ValueError("request %d emitted %d tokens in a step:"
                                     " one row cannot stand for them" % (rid, new))
                tap.keep(rid, slot_of[rid])
        steps.append(rec)
    return reqs, steps


def abandon(engine):
    """Clear what a cut-off drive left in the engine."""
    engine.queue = []
    for slot, _ in engine.pool.active_slots():
        engine.pool.evict(slot)


# --------------------------------------------------------------------------
# arithmetic on what drive() recorded (tested on hand-made schedules)
# --------------------------------------------------------------------------
def percentile(values, q):
    """The q-th percentile (0..100), linear between the two nearest order
    statistics; None of nothing."""
    v = sorted(values)
    if not v:
        return None
    k = (len(v) - 1) * q / 100.0
    lo, hi = int(math.floor(k)), int(math.ceil(k))
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def meets(limits, prompt_len, ttft_ms, itl_ms):
    """Both limits of a workload file: time to first token within
    `ttft_ms` + `ttft_ms_per_prompt_token` x the prompt's length (a prompt
    is prefilled in chunks, so its first token cannot come sooner than
    its chunks), mean gap between tokens within `itl_ms`."""
    return (ttft_ms <= limits["ttft_ms"]
            + limits.get("ttft_ms_per_prompt_token", 0.0) * prompt_len
            and itl_ms <= limits["itl_ms"])


def summarize(reqs, steps, open_s, close_s, limits, n_slots, t_max):
    """Everything the window says.  A request belongs to the window when
    it is DUE in it; a token, a step and its work when the step that made
    it ENDED in it.  A request is failed unless it finished OK with every
    token it asked for; a failed request misses both limits."""
    win = [r for r in reqs.values() if open_s <= r["due"] < close_s]
    ok = [r for r in win if r["status"] == "OK"
          and len(r["emitted"]) == r["max_new_tokens"]]
    # (prompt length, tokens, first token's ms from the due time, mean gap ms
    # or None of a single token) of each finished request
    timed = [(int(r["prompt"].size), len(r["emitted"]),
              1e3 * (r["emitted"][0] - r["due"]),
              1e3 * (r["emitted"][-1] - r["emitted"][0])
              / (len(r["emitted"]) - 1) if len(r["emitted"]) > 1 else None)
             for r in ok]
    met = sum(1 for p, _, t, g in timed if meets(limits, p, t, g or 0.0))
    inside = [s for s in steps if open_s <= s["t1"] < close_s]
    tokens = sum(1 for r in reqs.values() for t in r["emitted"]
                 if open_s <= t < close_s)

    def backlog(at):
        """Requests due by `at` and not finished by then."""
        return sum(1 for r in reqs.values() if r["due"] <= at and not (
            r["status"] is not None and r["emitted"]
            and r["emitted"][-1] <= at))

    n_steps = max(1, len(inside))
    cols = sum(s["prefill_cols"] + s["decode_cols"] for s in inside)
    return {
        "attempted": len(win), "failed": len(win) - len(ok),
        "statuses": sorted({str(r["status"]) for r in win}),
        "tokens_in_window": tokens, "steps_in_window": len(inside),
        "ttft_ms": [t for _, _, t, _ in timed],
        "itl_ms": [g for _, _, _, g in timed if g is not None],
        "attainment": 100.0 * met / len(win) if win else None,
        "per_request": [[p, n, round(t, 3), round(g or 0.0, 3)]
                        for p, n, t, g in timed],
        "queue_wait_ms": [1e3 * (r["admitted"] - r["due"]) for r in win
                          if r["admitted"] is not None],
        "lateness_ms": [1e3 * (r["submitted"] - r["due"]) for r in win
                        if r["submitted"] is not None],
        "backlog_mid": backlog(0.5 * (open_s + close_s)),
        "backlog_end": backlog(close_s),
        "occupancy": 100.0 * sum(s["active"] for s in inside)
        / (n_steps * n_slots),
        "prefill_column_share": (100.0 * sum(s["prefill_cols"] for s in inside)
                                 / cols if cols else None),
        "rows_used_share": 100.0 * sum(s["live_rows"] for s in inside)
        / (n_steps * n_slots * t_max),
        "work": work_of(inside),
    }


def work_of(steps):
    """What the adapter's closed forms are asked about a set of steps."""
    return {
        "steps": len(steps),
        "columns": sum(s["prefill_cols"] + s["decode_cols"] for s in steps),
        "context_sum": sum(s["context_sum"] for s in steps),
        "sampled": sum(s["sampled"] for s in steps),
        "rows_read": sum(s["rows_read"] for s in steps),
    }


def row_errors(rows, reference):
    """Each row's distance from the reference's row, [n, V] both: the norm
    of the difference over the norm of the reference's row about its mean
    (a constant added to a row of logits changes no probability, and the
    two sides add none)."""
    import numpy as np

    rows = np.asarray(rows, "float64")
    reference = np.asarray(reference, "float64")
    spread = reference - reference.mean(-1, keepdims=True)
    return (np.linalg.norm(rows - reference, axis=-1)
            / np.linalg.norm(spread, axis=-1))


def compare(checked, limits):
    """`correct`'s reference part.  `checked` = [(rid, rows, references,
    tokens)] of the watched greedy requests that finished: the rows [n, V]
    the timed step fetched, the reference's rows at the same positions
    (a list: one [n, V] for each precision the adapter admits; a row's
    error is its distance from the nearest), the n tokens served.  The
    numbers a limit may name: `logit_err_mean` and `logit_err_max`
    (row_errors over every compared row) and `off_argmax` (served tokens
    that are not the first choice of the row they were picked from: a
    greedy token is, exactly).  Nothing to compare is a failure, not a
    pass."""
    import numpy as np

    each = [np.stack([row_errors(rows, ref) for ref in refs])
            for _, rows, refs, _ in checked]          # [references, n]
    errs = [float(e) for per in each for e in per.min(0)]
    off = sum(int((np.argmax(rows, -1) != np.asarray(tokens)).sum())
              for _, rows, _, tokens in checked)
    out = {"requests": len(checked), "tokens": len(errs), "limits": limits,
           "logit_err_mean": sum(errs) / len(errs) if errs else None,
           "logit_err_max": max(errs, default=None),
           "off_argmax": off if errs else None,
           # against each reference alone, for the record
           "logit_err_mean_by_reference": (
               np.concatenate(each, 1).mean(1).tolist() if errs else None)}
    out["ok"] = bool(errs) and all(out[k] <= v for k, v in limits.items())
    return out


def pick_watch(schedule, n, seed):
    """The requests whose logits rows the Tap keeps, chosen before the
    window from what is scheduled: greedy ones (the ramp's too: the
    window's steps serve them), the longest sequence first, then a draw
    from the seed: n of them at the most, as rids."""
    import numpy as np

    greedy = [r for r in schedule if r["seed"] is None]
    if not greedy:
        return []
    greedy.sort(key=lambda r: (-(r["prompt"].size + r["max_new_tokens"]),
                               r["rid"]))
    rest = greedy[1:]
    pick = np.random.default_rng(int(seed)).permutation(len(rest))[:max(0, n - 1)]
    return [greedy[0]["rid"]] + sorted(rest[i]["rid"] for i in pick)


# --------------------------------------------------------------------------
# the traced slice
# --------------------------------------------------------------------------
def _trace_dir(ctx):
    return os.path.join(ctx["root"], ".bench_trace", ctx["cell"]["name"])


def _reduce_trace(ctx, out_dir):
    """(device reduction or None, serve spans or None) of the slice."""
    files = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return None, None
    from jax.profiler import ProfileData

    keep = ctx["args"].keep_trace
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(files[0], os.path.join(
            keep, ctx["cell"]["name"] + ".xplane.pb"))
    data = ProfileData.from_file(files[0])
    reduced = ctx["load_module"]("", "trace_reduce").reduce_profile(
        data, n_devices=ctx["chips"])
    spans = ctx["load_module"]("readers", "serve_span").reduce(data)
    return reduced, spans


# --------------------------------------------------------------------------
def run(ctx):
    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.serving import Request, ServingEngine

    cfg, work, log = ctx["cfg"], ctx["work"], ctx["log"]
    adapter = ctx["load_module"]("adapters", work["adapter"])
    seed, seconds = ctx["args"].seed, ctx["seconds"]
    eng, traffic = work["engine"], work["traffic"]
    vocab = cfg["model"]["vocab_size"]
    ramp_s, drain_s = float(work["ramp_seconds"]), float(work["drain_seconds"])
    annotate = jax.profiler.TraceAnnotation

    fake = None
    if work.get("fake_clock_step_s") is not None:
        # a rehearsal: the window is as many steps long on any host
        fake = FakeClock(work["fake_clock_step_s"])
        seconds = min(seconds, work["max_steps"] * fake.step_s)
    clock = ctx.get("clock") or fake or time.perf_counter
    sleep = ctx.get("sleep") or (fake.sleep if fake else time.sleep)
    step_done = ctx.get("step_done") or (fake.step_done if fake else None)

    # ---- set-up: weights, engine, warm-up, traffic ----
    place = fluid.CPUPlace() if ctx["rehearse"] else fluid.TPUPlace(0)
    scope = fluid.Scope()
    exe = fluid.Executor(place)
    # a test plants a fault beneath the timed path here (ctx["wrap_exe"]:
    # Executor -> Executor), as it hands in its clock; a run has none
    tap = Tap(ctx.get("wrap_exe", lambda e: e)(exe))
    built = adapter.build_serve(cfg, work)
    with fluid.scope_guard(scope):
        engine = ServingEngine(
            tap, built["hp"], n_slots=int(eng["n_slots"]),
            width=int(eng["width"]), t_max=int(eng["t_max"]),
            cache_dtype=eng["cache_dtype"])
        main = tap.program = engine.step_main
        t_weights = time.perf_counter()
        weights = adapter.make_weights(cfg, seed)
        params = main.global_block().all_parameters()
        if [tuple(p.shape) for p in params] != [tuple(w.shape) for w in weights]:
            raise SystemExit("serve: the adapter's parameters are not the "
                             "step program's, in order")
        for p, w in zip(params, weights):
            scope.set(p.name, w)   # the program is GIVEN its weights
        jax.block_until_ready(weights)
        # the reference draws its own from the seed again, after the window:
        # a second handle here would hold a second copy on the device once
        # the executable has laid the scope's arrays out its own way
        del weights
        exe.run(engine.cache_startup)
        t_ready = time.perf_counter()

        def make_request(r, now_step):
            return Request(rid=r["rid"], prompt=r["prompt"],
                           max_new_tokens=r["max_new_tokens"],
                           temperature=r["temperature"], top_k=r["top_k"],
                           top_p=r["top_p"], seed=r["seed"],
                           arrival=float(now_step))

        def iteration():
            with annotate("bench:serve_iter"):   # the loop's own span
                return engine.step()

        # two warm-up requests to their end: a prompt of several chunks
        # sampled with top-k and top-p, and a greedy one; every program
        # (step, slot reset) compiled or read from the cache, blocked on
        p_warm = min(2 * engine.width + 1, engine.t_max - 4)
        for i, how in enumerate((
                {"temperature": 0.8, "top_k": 8, "top_p": 0.9, "seed": 1},
                {"temperature": 1.0, "top_k": 0, "top_p": 1.0, "seed": None})):
            engine.submit(make_request(dict(
                how, rid=-1 - i, max_new_tokens=3,
                prompt=np.arange(1, 1 + p_warm) % vocab), engine.now))
        first_step_s = None
        while engine.queue or engine.pool.active_slots():
            iteration()
            if first_step_s is None:
                first_step_s = time.perf_counter() - t_ready
        segments = [("ramp", 0.0, ramp_s), ("window", ramp_s, seconds)]
        schedule = make_schedule(traffic, vocab, seed, segments)
        tap.watch = set(pick_watch(schedule, int(work["reference_requests"]),
                                   seed))
        setup_s = time.perf_counter() - ctx["t_start"]
        log("set-up %.2f s (weights + cache %.2f s, first step %.2f s); "
            "%d requests scheduled"
            % (setup_s, t_ready - t_weights, first_step_s, len(schedule)))

        # ---- ramp, window, drain ----
        open_s, close_s = ramp_s, ramp_s + seconds
        marks = {}

        def mark(name):
            def at():
                marks[name] = (exe.compile_count, exe.host_feed_ms,
                               dict(engine.counters))
            return at

        reqs, steps = drive(
            engine, schedule, clock, sleep, close_s + drain_s, make_request,
            step=iteration, step_done=step_done, tap=tap,
            on_time=[(open_s, mark("open")), (close_s, mark("close"))])
        tap.watch = set()   # the traced slice keeps nothing
        for name in ("open", "close"):
            if name not in marks:
                mark(name)()
        unfinished = len(engine.queue) + len(engine.pool.active_slots())
        abandon(engine)
        # ---- closed ----
        memory_stats = [d.memory_stats() or {} for d in ctx["devices"]]
        peak_bytes = max(int(st.get("peak_bytes_in_use", 0))
                         + int(st.get("peak_bytes_reserved", 0))
                         for st in memory_stats)
        kv_pool = engine.kv_pool_bytes(scope)["max_device_bytes"]

        limits = work["limits"]
        n_slots, t_max = engine.n_slots, engine.t_max
        s = summarize(reqs, steps, open_s, close_s, limits, n_slots, t_max)
        compiles_in_window = marks["close"][0] - marks["open"][0]
        host_feed_ms = ((marks["close"][1] - marks["open"][1])
                        / max(1, s["steps_in_window"]))

        # ---- traced slice: the same traffic, another draw, profiler on
        # between the ramp's end and trace_seconds later ----
        trace, spans, slice_work = None, None, None
        if ctx["args"].trace:
            tr_s = float(work["trace_seconds"])
            tr_schedule = make_schedule(
                traffic, vocab, seed + 2,
                [("ramp", 0.0, ramp_s), ("window", ramp_s, tr_s)])
            out_dir = _trace_dir(ctx)
            shutil.rmtree(out_dir, ignore_errors=True)
            os.makedirs(out_dir)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            tracing = []

            def start():
                jax.profiler.start_trace(out_dir, profiler_options=options)
                tracing.append(True)

            def stop():
                if tracing:
                    tracing.pop()
                    jax.profiler.stop_trace()

            try:
                _, tr_steps = drive(
                    engine, tr_schedule, clock, sleep, ramp_s + tr_s,
                    make_request, step=iteration, step_done=step_done,
                    on_time=[(ramp_s, start), (ramp_s + tr_s, stop)])
            finally:
                stop()
                abandon(engine)
            slice_work = work_of([x for x in tr_steps
                                  if ramp_s <= x["t0"] and x["t1"] <= ramp_s + tr_s])
            trace, spans = _reduce_trace(ctx, out_dir)
            shutil.rmtree(out_dir, ignore_errors=True)

        # ---- the program's state is freed; then the plain reference ----
        for name in engine.cache_names:
            scope.erase(name)
    t_ref = time.perf_counter()
    weights = adapter.make_weights(cfg, seed)
    checked = []
    for rid in sorted(tap.rows):
        r = reqs[rid]
        if r["status"] != "OK" or len(tap.rows[rid]) != len(r["tokens"]):
            continue   # unfinished: `failed` or `complete` speaks of it
        checked.append((rid, np.stack(tap.rows.pop(rid)),
                        adapter.reference_logits(cfg, work, weights,
                                                 r["prompt"], r["tokens"]),
                        np.asarray(r["tokens"])))
    reference = compare(checked, dict(adapter.SERVE_TOLERANCE))
    reference["seconds"] = time.perf_counter() - t_ref
    del checked, weights

    complete = all(len(r["emitted"]) == r["max_new_tokens"]
                   for r in reqs.values() if r["status"] == "OK")
    correct = (compiles_in_window == 0 and complete and s["failed"] == 0
               and reference["ok"])

    wf = adapter.serve_flops(cfg, s["work"]["columns"], s["work"]["context_sum"],
                             s["work"]["sampled"])
    rate = s["tokens_in_window"] / seconds / ctx["chips"]
    mfu = 100.0 * wf / seconds / (ctx["chips"] * ctx["peak"]["flops_per_s"])
    # a window none of whose requests finished has no tail to take: it
    # reads the longest a request could have been waited for (and the run
    # is not correct: every one of its requests failed)
    longest_ms = 1e3 * (seconds + drain_s)
    ttft90, itl90 = percentile(s["ttft_ms"], 90), percentile(s["itl_ms"], 90)
    metrics = {"serve_tokens_per_s": rate, "serve_mfu": mfu,
               "setup_s": setup_s,
               "ttft_ms_p90": longest_ms if ttft90 is None else ttft90,
               "itl_ms_p90": longest_ms if itl90 is None else itl90}
    step_ms = [1e3 * (x["t1"] - x["t0"]) for x in steps
               if open_s <= x["t1"] < close_s]
    counters = {
        "compiles_in_window": compiles_in_window,
        "host_feed_ms": host_feed_ms,
        "first_step_s": first_step_s,
        "peak_hbm_gib": peak_bytes / 2.0 ** 30 if peak_bytes else None,
        "serve_occupancy": s["occupancy"],
        "serve_prefill_column_share": s["prefill_column_share"],
        "serve_queue_wait_ms_p50": percentile(s["queue_wait_ms"], 50),
        "generator_lateness_ms_p90": percentile(s["lateness_ms"], 90),
        "serve_slo_attainment": s["attainment"],
        "ttft_ms_p50": percentile(s["ttft_ms"], 50),
        "itl_ms_p50": percentile(s["itl_ms"], 50),
        "kv_pool_gib": kv_pool / 2.0 ** 30,
        "kv_rows_used_share": s["rows_used_share"],
    }
    ctx.update({
        "exe": exe, "main": main, "scope": scope, "trace": trace,
        "counters": counters, "serve_spans": spans,
        "serve_slice_work": slice_work, "serve_adapter": adapter,
        # busy_mfu's question, asked of a serve step: what a step of the
        # traced slice requires over the time the device was busy with it
        "flops_per_step": (adapter.serve_flops(
            cfg, slice_work["columns"], slice_work["context_sum"],
            slice_work["sampled"]) / slice_work["steps"]
            if slice_work and slice_work["steps"] else None),
        # the train cells' profile of Executor.run drives a training batch
        # through ctx["main"]: it has nothing to drive here
        "program_profile": None,
    })
    detail = {
        "window_s": seconds, "ramp_s": ramp_s, "drain_s": drain_s,
        "requests_scheduled": len(schedule), "attempted": s["attempted"],
        "failed": s["failed"], "statuses": s["statuses"],
        "unfinished_at_end": unfinished,
        "tokens_in_window": s["tokens_in_window"],
        "steps_in_window": s["steps_in_window"],
        "samples": {"ttft": len(s["ttft_ms"]), "itl": len(s["itl_ms"])},
        "ttft_ms": {q: percentile(s["ttft_ms"], q) for q in (50, 90, 99)},
        "itl_ms": {q: percentile(s["itl_ms"], q) for q in (50, 90, 99)},
        "queue_wait_ms_p50": counters["serve_queue_wait_ms_p50"],
        "lateness_ms_p90": counters["generator_lateness_ms_p90"],
        "loop_step_ms": {q: percentile(step_ms, q) for q in (50, 90)},
        "attainment": s["attainment"], "limits": limits,
        "backlog_mid": s["backlog_mid"], "backlog_end": s["backlog_end"],
        # prompt length, tokens, ttft ms, mean gap ms of each finished request
        "per_request": s["per_request"],
        "occupancy": s["occupancy"],
        "prefill_column_share": s["prefill_column_share"],
        "rows_used_share": s["rows_used_share"], "work": s["work"],
        "window_flops": wf, "compiles_in_window": compiles_in_window,
        "engine": {"n_slots": n_slots, "width": engine.width, "t_max": t_max,
                   "cache_dtype": eng["cache_dtype"], "kv_pool_bytes": kv_pool},
        "engine_counters": {k: marks["close"][2][k] - marks["open"][2][k]
                            for k in marks["close"][2]},
        "setup": {"weights_cache_s": t_ready - t_weights,
                  "first_step_s": first_step_s},
        "serve_spans": spans, "slice_work": slice_work,
        "peak_hbm_gib": peak_bytes / 2.0 ** 30,
        "memory_stats": memory_stats, "metrics": metrics,
        "reference": reference,
    }
    # each number compared beside its limit: the run's last lines on stderr
    compared = {k: [reference[k], v] for k, v in reference["limits"].items()}
    compared.update({
                "failed": [s["failed"], 0],
                "compiles_in_window": [compiles_in_window, 0],
                "complete": [int(complete), 1]})
    detail["zz_compared"] = compared
    print("compared (value, limit): " + json.dumps(compared),
          file=sys.stderr, flush=True)
    return {"correct": correct, "attempted": s["attempted"],
            "failed": s["failed"], "metrics": metrics,
            "memory_peak_bytes": peak_bytes, "detail": detail}
