"""The one tracing system: names inside the compiled step
(core/trace.py's `<op_role>/<op type>/<index>` scopes in the optimized
HLO), RecordEvent on the device trace's clock (`paddle_tpu:<name>` on the
host plane of any running JAX trace) beside its chrome-trace list, the
Executor's spans (one set per run, the same from every run path),
Executor.compiled_steps, and the set-up ledger (profiler.phases() /
counters(): what happens once a process or once a compile, recorded
always, and never by a steady step)."""

import collections
import glob
import os
import re
import threading

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import layers, profiler

ROLES = ("forward", "backward", "optimize", "lrsched", "loss", "rpc")
SCOPE = re.compile(r"(?:^|[/(])(%s)/([\w.]+)/(\d+)(?=[/)]|$)"
                   % "|".join(ROLES))
INNER = ("feed_upload", "state_gather", "executor_run", "state_commit")

# everything JAX announces through jax.monitoring while these tests run:
# what paddle_tpu's own listeners (profiler._on_compile_*) would be
# called for
JAX_ANNOUNCED = []
jax.monitoring.register_event_listener(
    lambda event, **kw: JAX_ANNOUNCED.append(event))
jax.monitoring.register_event_duration_secs_listener(
    lambda event, seconds, **kw: JAX_ANNOUNCED.append(event))
jax.monitoring.register_event_time_span_listener(
    lambda event, start, end, **kw: JAX_ANNOUNCED.append(event))


def _small_train_program():
    """fc -> loss -> SGD, plus a While sub-block that counts to 3."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.data("y", shape=[1])
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        i = layers.fill_constant([1], "float32", 0.0)
        n = layers.fill_constant([1], "float32", 3.0)
        total = layers.fill_constant([1], "float32", 0.0)
        cond = layers.less_than(i, n)
        loop = layers.While(cond)
        with loop.block():
            layers.assign(layers.elementwise_add(total, i), total)
            layers.increment(i, 1.0)
            layers.less_than(i, n, cond=cond)
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss, total


def _feed(batch=2):
    return {"x": np.ones((batch, 4), "float32"),
            "y": np.ones((batch, 1), "float32")}


def test_scopes_reach_the_optimized_hlo():
    main, startup, loss, total = _small_train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[loss, total])
    (text,) = exe.compiled_hlo(main)
    blocks = [main.block(i).ops for i in range(main.num_blocks)]
    seen, lowered = set(), 0
    for line in text.splitlines():
        op_name = re.search(r'op_name="(jit\(program_step\)/[^"]*)"', line)
        if not op_name or re.search(r"\bparameter\(", line):
            continue
        lowered += 1
        path = SCOPE.findall(op_name.group(1))
        assert path, "no Fluid scope on a lowered instruction: " + line
        # the outermost scope is an op of the main block; one nested in
        # it is an op of a sub-block
        role, typ, idx = path[0]
        op = blocks[0][int(idx)]
        assert (op.type, op.attrs["op_role"]) == (typ, role), line
        for role, typ, idx in path[1:]:
            assert any(len(ops) > int(idx) and ops[int(idx)].type == typ
                       and ops[int(idx)].attrs["op_role"] == role
                       for ops in blocks[1:]), line
        seen.update((r, t) for r, t, _ in path)
    assert lowered > 10
    types = {t for _, t in seen}
    assert {"mul", "mul_grad", "sgd", "while"} <= types, types
    assert {r for r, _ in seen} >= {"forward", "backward", "optimize"}
    # an op of the While body nests under its parent's scope
    assert re.search(r"forward/while/\d+/[^\"]*forward/increment/\d+", text)


def _host_spans(trace_dir):
    """[(start, end, name, stats)] of the program's spans on the host
    plane of the trace under `trace_dir`, in start order."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("paddle_tpu:"):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name[len("paddle_tpu:"):],
                                  {k: v for k, v in e.stats}))
    return sorted(spans, key=lambda s: (s[0], -s[1]))


def _collective_program():
    """A two-layer MLP whose dense gradients all-reduce in the step:
    DistributeTranspiler(mode="collective") over two replicas."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.data("y", shape=[1])
        pred = layers.fc(layers.fc(x, size=8, act="relu"), size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    config = fluid.DistributeTranspilerConfig()
    config.mode = "collective"
    transpiler = fluid.DistributeTranspiler(config=config)
    transpiler.transpile(0, program=main, pservers="", trainers=2,
                         sync_mode=True, startup_program=startup)
    return transpiler.get_trainer_program(), startup, loss


def _gpt2_program(path):
    """A one- or two-layer GPT-2 on two devices: GSPMD over mp ("spmd"),
    or two pipeline stages of two microbatches ("pipeline")."""
    from paddle_tpu.models import gpt2
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.transpiler.pipeline import pipeline_program

    class TinyHP(gpt2.GPT2Config):
        vocab_size, n_ctx, d_model = 64, 16, 32
        n_layer = 1 if path == "spmd" else 2
        n_head, d_inner, dropout, tie_embeddings = 4, 64, 0.0, False

    old_main = fluid.framework.switch_main_program(fluid.Program())
    old_startup = fluid.framework.switch_startup_program(fluid.Program())
    try:
        main, startup, _, fetches = gpt2.gpt2_lm_program(
            TinyHP, seq_len=8, lr=3e-3,
            mesh=make_mesh({"dp": 1, "mp": 2}, devices=jax.devices()[:2])
            if path == "spmd" else None)
        if path == "pipeline":
            main = pipeline_program(
                main, make_mesh({"pp": 2}, devices=jax.devices()[:2]),
                n_microbatches=2, schedule="1f1b")
    finally:
        fluid.framework.switch_main_program(old_main)
        fluid.framework.switch_startup_program(old_startup)
    return (main, startup, fetches,
            lambda batch: gpt2.make_fake_lm_batch(batch, 8, TinyHP, seed=0))


def _ledger_mark(exe=None):
    """Where the set-up ledger, its counters, JAX's announcements and an
    executor's compile_count stand."""
    return (len(profiler.phases()), profiler.counters(), len(JAX_ANNOUNCED),
            exe.compile_count if exe is not None else 0)


Traced = collections.namedtuple(
    "Traced", "path runs spans main startup setup_phases")
# one Executor.run under the trace: did compile_count rise, were numpy
# fetches asked for, and the _ledger_mark before and after it
Run = collections.namedtuple("Run", "compiled as_numpy before after")


def _traced_runs(tmp_path_factory, path):
    """Build a train program, run its startup program and one step, then
    five more steps under a plain jax.profiler trace (three steady ones,
    one with numpy fetches, one at a new batch size and a steady one
    after it) through `path`."""
    from paddle_tpu.core import scope as scope_mod

    n_before = len(profiler.phases())
    if path in ("spmd", "pipeline"):
        main, startup, fetches, feed = _gpt2_program(path)
    elif path == "collective":
        main, startup, loss = _collective_program()
        fetches, feed = [loss], _feed
    else:
        main, startup, loss, _ = _small_train_program()
        fetches, feed = [loss], _feed

    trace_dir = str(tmp_path_factory.mktemp("trace_" + path))
    runs = []
    with fluid.scope_guard(scope_mod.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed(2), fetch_list=fetches)  # compile outside
        setup_phases = profiler.phases()[n_before:]
        jax.profiler.start_trace(trace_dir)
        try:
            for batch, as_numpy in ((2, False), (2, False), (2, True),
                                    (4, False), (4, False)):
                before = _ledger_mark(exe)
                out = exe.run(main, feed=feed(batch), fetch_list=fetches,
                              return_numpy=as_numpy)
                after = _ledger_mark(exe)
                runs.append(Run(after[3] > before[3], as_numpy, before,
                                after))
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
    return Traced(path, runs, _host_spans(trace_dir), main, startup,
                  setup_phases)


@pytest.fixture(scope="module", params=[
    "fast", "spmd", "collective",
    pytest.param("pipeline", marks=pytest.mark.slow)])
def traced(request, tmp_path_factory):
    if request.param != "fast" and len(jax.devices()) < 2:
        pytest.skip("needs two virtual devices")
    return _traced_runs(tmp_path_factory, request.param)


def _calls(spans):
    """[(outer span, [spans inside it in time])] per executor.run."""
    outer = [s for s in spans if s[2] == "executor.run"]
    return [(o, [s for s in spans if s is not o
                 and o[0] <= s[0] and s[1] <= o[1]]) for o in outer]


def test_every_run_emits_one_nested_set_of_spans(traced):
    path, runs, spans = traced[:3]
    calls = _calls(spans)
    assert len(calls) == len(runs)
    assert sum(len(inside) for _, inside in calls) + len(calls) \
        == len(spans), "a span outside every executor.run"
    for (outer, inside), (compiled, as_numpy, _, _) in zip(calls, runs):
        names = [s[2] for s in inside]
        for name in INNER:
            assert names.count(name) == 1, (path, names)
        assert names.count("fetch_to_host") == (1 if as_numpy else 0)
        # in the order the run goes through them
        order = [n for n in names if n in INNER]
        assert order == list(INNER), order
        # a first run at a signature takes the slow path; steady ones the
        # memoised one (a mesh path has one route for both)
        want = path if path != "fast" else ("slow" if compiled else "fast")
        assert outer[3].get("path") == want, outer


def test_a_run_span_carries_the_number_its_steps_rng_key_folds(traced):
    """`step` on every executor.run span, whichever path ran it (the
    "fast" runs take the slow path at the new batch size): the startup
    program took 0 and the compile outside the trace 1.  The number is the
    one Executor.step_stats keeps a step's statistics under."""
    calls = _calls(traced.spans)
    assert [int(outer[3]["step"]) for outer, _ in calls] == [2, 3, 4, 5, 6]
    if traced.path == "fast":
        assert [outer[3]["path"] for outer, _ in calls] == [
            "fast", "fast", "fast", "slow", "fast"]


def test_the_run_paths_emit_the_same_names(traced):
    spans = traced.spans
    names = {s[2] for s in spans}
    assert names == {"executor.run", "trace_compile", "fetch_to_host",
                     *INNER}


def test_trace_compile_exactly_when_compile_count_rises(traced):
    runs, spans = traced.runs, traced.spans
    assert [r.compiled for r in runs] == [False, False, False, True, False]
    for (outer, inside), run in zip(_calls(spans), runs):
        compiles = [s for s in inside if s[2] == "trace_compile"]
        # the block's analysis at the miss, then the first call
        assert len(compiles) == (2 if run.compiled else 0), (outer, compiles)
        for s in compiles:  # the cause is on the span
            assert "[4, " in s[3]["feed_sig"], s


# ---- the set-up ledger: profiler.phases() and counters() ----

COMPILE_FIELDS = ("trace_s", "lower_s", "backend_compile_s")


def test_phases_arrive_in_the_order_set_up_runs_in(traced):
    """import, then the build, then the startup program's trace_compile,
    then the train step's: each closed, no shorter than what JAX reported
    inside it, naming its program and path."""
    first = profiler.phases()[0]
    assert (first["name"], first["depth"]) == ("import", 0)
    records = traced.setup_phases
    assert first["t1"] <= records[0]["t0"]
    names = [r["name"] for r in records]
    build = [n for n in names if n.startswith("build.")]
    assert names == build + ["trace_compile", "trace_compile"], names
    assert "build.minimize" in build and "build.backward" in build
    if traced.path == "spmd":  # gpt2's builder applies its fuse passes
        passes = [r["args"]["pass"] for r in records
                  if r["name"] == "build.pass"]
        assert "matmul_epilogue_fuse_pass" in passes, passes
    for r in records:
        assert r["t1"] >= r["t0"], r
        assert r["thread"] == threading.get_ident()
    by_name = {r["name"]: r for r in records}
    assert by_name["build.minimize"]["depth"] == 0
    assert by_name["build.backward"]["depth"] == 1  # minimize calls it
    for r in records[1:]:  # in the order they opened
        assert r["t0"] >= records[0]["t0"]
    want_path = "flat" if traced.path == "fast" else traced.path
    for r, program in zip(records[-2:], (traced.startup, traced.main)):
        args = r["args"]
        assert args["program"] == id(program) and r["depth"] == 0
        # (a startup program carries no mesh stamp unless its builder
        # gave it one: it may run flat beside a mesh-path train step)
        assert args["path"] in (want_path, "flat")
        inside = sum(args.get(f, 0.0) for f in COMPILE_FIELDS)
        # JAX's spans are on time.time(), the record on perf_counter()
        assert 0 < inside <= r["t1"] - r["t0"] + 1e-3, r
        assert 0 <= args["analyse_s"] <= r["t1"] - r["t0"]
        assert all(args[f] > 0 for f in COMPILE_FIELDS), r
    assert records[-2]["args"]["feed_sig"] == ""  # a startup program's
    assert "[2, " in records[-1]["args"]["feed_sig"]
    assert records[-1]["args"]["path"] == want_path


def test_steady_steps_append_no_phase_and_fire_no_listener(traced):
    steady = [r for r in traced.runs if not r.compiled]
    assert len(steady) == 4
    for run in steady:
        assert run.after == run.before, (
            "a steady Executor.run opened a phase, counted, or made JAX "
            "announce an event", run)


def test_a_new_feed_shape_appends_one_trace_compile(traced):
    (run,) = [r for r in traced.runs if r.compiled]
    assert run.after[3] == run.before[3] + 1  # compile_count, every path
    assert run.after[0] == run.before[0] + 1  # two spans, ONE record
    record = profiler.phases()[run.before[0]]
    assert record["name"] == "trace_compile"
    assert "[4, " in record["args"]["feed_sig"]
    assert record["args"]["program"] == id(traced.main)
    assert run.after[2] > run.before[2]  # and JAX announced its compile
    assert run.after[1] == run.before[1]  # into the record, not a total


def test_a_second_compile_of_a_step_reads_the_cache(tmp_path):
    """With a persistent cache directory, the first compile of a step
    misses (and writes) and a second executor's compile of the same step
    reports a hit, no miss, and the read's time."""
    from jax.experimental.compilation_cache import compilation_cache

    keep = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    try:
        main, startup, loss, _ = _small_train_program()
        records = []
        for _ in range(2):
            exe = fluid.Executor(fluid.CPUPlace())
            with fluid.scope_guard(fluid.Scope()):
                exe.run(startup)
                exe.run(main, feed=_feed(), fetch_list=[loss])
            records.append(profiler.phases()[-1]["args"])
    finally:
        for name, value in keep.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    cold, warm = records
    assert cold["program"] == warm["program"] == id(main)
    assert cold.get("cache_misses", 0) >= 1 and "cache_hits" not in cold
    assert warm["cache_hits"] >= 1 and warm.get("cache_misses", 0) == 0
    assert 0 < warm["cache_read_s"] <= warm["backend_compile_s"]


def test_phases_are_spans_of_a_running_trace(tmp_path):
    trace_dir = str(tmp_path)
    jax.profiler.start_trace(trace_dir)
    try:
        main, startup, loss, _ = _small_train_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(trace_dir)
    by_name = collections.defaultdict(list)
    for span in spans:
        by_name[span[2]].append(span)
    (minimize,), (backward,) = by_name["build.minimize"], by_name[
        "build.backward"]
    assert minimize[0] <= backward[0] and backward[1] <= minimize[1]
    analysis, first_call = by_name["trace_compile"]
    for span in (analysis, first_call):
        assert span[3]["path"] == "flat"
        assert int(span[3]["program"]) == id(startup)
    (outer,) = by_name["executor.run"]
    assert outer[0] <= analysis[0] and first_call[1] <= outer[1]


def test_the_ledger_is_bounded(monkeypatch):
    monkeypatch.setattr(profiler, "PHASE_LIMIT", len(profiler.phases()) + 2)
    dropped = profiler.counters().get("phases_dropped", {"calls": 0})
    for i in range(5):
        with profiler.phase("test.bounded", i=i) as ph:
            assert ph.record["t1"] is None
        assert ph.record["t1"] >= ph.record["t0"]  # dropped or not
    kept = [r["args"]["i"] for r in profiler.phases()
            if r["name"] == "test.bounded"]
    assert kept == [0, 1] and len(profiler.phases()) == profiler.PHASE_LIMIT
    assert profiler.counters()["phases_dropped"]["calls"] \
        == dropped["calls"] + 3


def test_a_compile_on_another_thread_does_not_nest_under_the_main_one():
    """A prefetch thread's compile while the main thread is inside a
    phase: its record has depth 0, and what JAX reports on that thread
    goes to its record, not to the main thread's open trace_compile."""
    main, startup, loss, _ = _small_train_program()
    scope = fluid.Scope()
    done = []

    def worker():
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        done.append(threading.get_ident())

    n = len(profiler.phases())
    with profiler.phase("trace_compile", why="the main thread's") as mine:
        thread = threading.Thread(target=worker)
        thread.start()
        thread.join(timeout=120)
        assert not thread.is_alive() and done
    ours, theirs = profiler.phases()[n:]
    assert ours["args"] == {"why": "the main thread's"}  # no trace_s
    assert (theirs["name"], theirs["depth"]) == ("trace_compile", 0)
    assert theirs["thread"] == done[0] != ours["thread"]
    assert theirs["args"]["backend_compile_s"] > 0
    assert mine.record["t0"] <= theirs["t0"] <= theirs["t1"] \
        <= mine.record["t1"]


def test_infer_shape_is_counted_and_a_phase_snapshots_the_counters():
    """A phase carries counters() as it opened and as it closed: what
    was counted inside it is their difference, by order and no clock."""
    none = {"calls": 0, "seconds": 0.0}
    before = profiler.counters().get("infer_shape", none)
    with profiler.phase("test.build") as ph:
        _small_train_program()
    after = profiler.counters()["infer_shape"]
    assert after["calls"] >= before["calls"] + 10  # one a layer's op
    assert after["seconds"] > before["seconds"]
    record = profiler.phases()[-3]  # then build.minimize > build.backward
    assert record["name"] == "test.build"
    assert record["counters"].get("infer_shape", none) == before
    assert record["counters_end"]["infer_shape"] == after == \
        ph.record["counters_end"]["infer_shape"]


def test_record_event_off_reads_no_clock_and_keeps_no_event(monkeypatch):
    def no_clock(*a):
        raise AssertionError("a span read the clock with nothing collecting")

    profiler.reset_profiler()
    for name in ("time", "perf_counter", "monotonic"):
        monkeypatch.setattr(profiler.time, name, no_clock)
    with profiler.RecordEvent("idle", cat="feed", why="nothing collects"):
        pass
    with profiler.record_event("idle"):
        pass
    monkeypatch.undo()
    assert profiler.comm_compute_split([], events=None) == {
        "comm_ms": 0, "compute_ms": 0, "comm_fraction": 0.0}
    assert profiler.stop_profiler(profile_path=None) == []


def test_record_event_feeds_chrome_list_and_trace(tmp_path):
    """One enter/exit, two collectors: bare name (+ args, cat) in the
    chrome list, `paddle_tpu:` name with the args as stats in the trace
    that `profiler(trace_dir=)` runs."""
    import json

    out, trace_dir = str(tmp_path / "prof"), str(tmp_path / "xplane")
    with profiler.profiler("All", profile_path=out, trace_dir=trace_dir):
        with profiler.RecordEvent("outer", cat="feed", step=7):
            with profiler.RecordEvent("inner"):
                pass
    with open(out + ".json") as f:
        events = {e["name"]: e for e in json.load(f)["traceEvents"]}
    assert set(events) == {"outer", "inner"}
    assert events["outer"]["cat"] == "feed"
    assert events["outer"]["args"] == {"step": 7}
    assert "args" not in events["inner"]
    spans = _host_spans(trace_dir)
    assert [s[2] for s in spans] == ["outer", "inner"]
    assert spans[0][3] == {"step": 7}
    assert spans[0][0] <= spans[1][0] and spans[1][1] <= spans[0][1]


def test_compiled_steps_name_feeds_fetches_and_path():
    main, startup, loss, total = _small_train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    assert exe.compiled_steps(main) == []
    exe.run(main, feed=_feed(), fetch_list=[loss])
    exe.run(main, feed=_feed(), fetch_list=[loss])  # same executable
    exe.run(main, feed=_feed(4), fetch_list=[loss, total])
    small, large = exe.compiled_steps(main)
    assert (small.path, large.path) == ("flat", "flat")
    assert small.feeds == {"x": ((2, 4), "float32"),
                           "y": ((2, 1), "float32")}
    assert large.feeds["x"] == ((4, 4), "float32")
    assert small.fetches == [loss.name]
    assert large.fetches == [loss.name, total.name]
    # a reader that runs the recorded step again hits the same executable
    compiles = exe.compile_count
    exe.run(main, feed={n: np.ones(s, d) for n, (s, d) in large.feeds.items()},
            fetch_list=large.fetches)
    assert exe.compile_count == compiles
    # compiled_hlo is its thin client: one optimized module per executable
    texts = exe.compiled_hlo(main)
    assert len(texts) == 2 and texts[0] == small.hlo()
    assert all(t.startswith("HloModule jit_program_step") for t in texts)
    assert exe.compiled_steps(startup)[0].fetches == []


# ---- fluid.name_scope: op_namescope, and its part of the scope path ----

def _scoped_train_program():
    """Two fc layers under nested name scopes and one under none, a loss
    and SGD; the two scoped layers share one weight by name."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.data("y", shape=[1])
        shared = fluid.ParamAttr(name="shared.w")
        with fluid.name_scope("outer"):
            h = layers.fc(x, size=4, param_attr=shared, bias_attr=False)
            with fluid.name_scope("inner"):
                h = layers.fc(h, size=4, act="relu")
        with fluid.name_scope("other"):
            h = layers.fc(h, size=4, param_attr=shared, bias_attr=False)
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def test_name_scope_stamps_the_ops_built_under_it():
    main, _, _ = _scoped_train_program()
    ops = main.global_block().ops
    by_scope = {}
    for op in ops:
        by_scope.setdefault(op.attrs.get("op_namescope"), []).append(op.type)
    # nested scopes join; an op built under none carries no attribute
    assert set(by_scope) == {None, "outer", "outer/inner", "other"}
    assert by_scope["outer/inner"].count("mul") == 1
    assert "relu" in by_scope["outer/inner"]
    # grad ops inherit their forward op's
    for scope in ("outer", "outer/inner", "other"):
        assert by_scope[scope].count("mul_grad") == by_scope[scope].count(
            "mul") == 1
    assert "relu_grad" in by_scope["outer/inner"]
    assert by_scope[None].count("mul") == by_scope[None].count("mul_grad") == 1
    # the fan-in of the weight that two scopes share belongs to neither
    (fan_in,) = [op for op in ops if op.type == "sum"]
    assert len(fan_in.inputs["X"]) == 2
    assert "op_namescope" not in fan_in.attrs
    # an empty prefix adds nothing, and the stack unwinds
    with fluid.name_scope(""), fluid.name_scope(None):
        assert fluid.framework._name_scope_stack == []
    assert fluid.framework._name_scope_stack == []


@pytest.mark.parametrize("prefix", ["a/b", "a.b", "ut-1", "two words"])
def test_name_scope_refuses_a_prefix_no_reader_of_the_scopes_would_match(
        prefix):
    """core/trace.py joins nested prefixes by "." and the readers match
    the joined word with [\\w.]+: a prefix with another character would
    drop its ops out of every scope metric without a word."""
    with pytest.raises(ValueError, match="name_scope"):
        with fluid.name_scope(prefix):
            pass
    assert fluid.framework._name_scope_stack == []


def test_name_scopes_reach_the_optimized_hlo_as_a_nested_part():
    """`<role>/<type>/<index>` first, as every reader of the scopes
    matches it, then `<role>/<scopes joined by .>/<how many>`."""
    main, startup, loss = _scoped_train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=_feed(), fetch_list=[loss])
    (text,) = exe.compiled_hlo(main)
    paths = set()
    for op_name in re.findall(r'op_name="jit\(program_step\)/([^"]*)"', text):
        found = SCOPE.findall(op_name)
        assert found, op_name
        paths.add(tuple(found))
    ops = main.global_block().ops
    nested = {}
    for path in paths:
        role, typ, idx = path[0]
        op = ops[int(idx)]
        assert (op.type, op.attrs["op_role"]) == (typ, role)
        want = op.attrs.get("op_namescope")
        if want is None:
            assert len(path) == 1, path
            continue
        assert path[1] == (role, want.replace("/", "."),
                           str(want.count("/") + 1)), path
        nested.setdefault(path[1][1], set()).add(typ)
    assert {"outer", "outer.inner", "other"} <= set(nested)
    assert "mul_grad" in nested["outer.inner"]


def _op_list_digest(main):
    import hashlib
    import json

    rows = [[op.type, op.attrs.get("op_role"),
             sorted((k, list(v)) for k, v in op.inputs.items()),
             sorted((k, list(v)) for k, v in op.outputs.items()),
             op.attrs.get("op_namescope")]
            for b in range(main.num_blocks) for op in main.block(b).ops]
    return hashlib.sha1(json.dumps(rows).encode()).hexdigest(), len(rows)


def _lm_programs():
    from paddle_tpu.models import gpt2, lfm2, olmoe

    class G(gpt2.GPT2Config):
        vocab_size, n_ctx, d_model, n_layer, n_head = 100, 16, 32, 2, 2

    class O(olmoe.OLMoEConfig):
        vocab_size, hidden_size, intermediate_size = 300, 64, 32
        num_hidden_layers, num_attention_heads, num_key_value_heads = 2, 2, 2
        num_experts, num_experts_per_tok = 8, 2

    class L(lfm2.LFM2MoEConfig):
        vocab_size, hidden_size, intermediate_size = 256, 64, 96
        moe_intermediate_size, num_hidden_layers, num_dense_layers = 32, 3, 1
        layer_types = ["conv", "full_attention", "conv"]
        num_attention_heads, num_key_value_heads = 2, 1
        num_experts, num_experts_per_tok = 8, 2

    return {"gpt2": (gpt2.gpt2_lm_program, G),
            "olmoe": (olmoe.olmoe_lm_program, O),
            "lfm2": (lfm2.lfm2_lm_program, L)}


# (sha1 of the op list, ops) of each builder's train program at the commit
# before name scopes became real and lm_train_program took a trunk with
# its own per-token cost (PR 31's df8835e, computed there by the same
# function; the CPU-optimized HLO of all six was compared by hand then
# and differed in nothing but file names).  `lfm2` under bf16 is re-taken
# from PR 58's tree by the same function: the AMP pass now runs the
# grouped values' `expand` in bfloat16 (contrib/mixed_precision._MOVE_OPS),
# one cast fewer; the other five did not move
BEFORE = {
    ("gpt2", False): ("98754ed59062a5164c94a1b20a988527113db282", 132),
    ("gpt2", True): ("9c58fae632f1cd1321b1f1f8c1e5ee9daa5683b5", 216),
    ("olmoe", False): ("07649c6d3a776b64678eec1b24c7761f184934cd", 150),
    ("olmoe", True): ("ea273ac154b2fd554d4fc9e727a4741159455bb8", 232),
    ("lfm2", False): ("f38f922df3ec63949ba63a4c1bd662a6bd376e1b", 147),
    ("lfm2", True): ("61f4ad66a9b7b7a27ea84af41e086c2e32712dcd", 231),
}


def _scoped_lm_programs():
    from paddle_tpu.models import kanana2, ouro

    class U(ouro.OuroConfig):
        vocab_size, hidden_size, intermediate_size = 256, 64, 96
        num_hidden_layers, num_attention_heads, num_key_value_heads = 2, 2, 2
        head_dim, total_ut_steps = 32, 3
        layer_types = ["full_attention"] * 2

    class K(kanana2.Kanana2Config):
        vocab_size, hidden_size, intermediate_size = 256, 64, 96
        moe_intermediate_size, num_hidden_layers = 32, 3
        num_attention_heads = num_key_value_heads = 2
        kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim = 32, 16, 8
        v_head_dim, n_routed_experts, num_experts_per_tok = 16, 8, 2

    return {"ouro": (ouro.ouro_lm_program, U),
            "kanana2": (kanana2.kanana2_lm_program, K)}


# the builders whose ops DO carry name scopes (the digest reads them):
# Ouro's as it was at PR 37's parent (ec9cdf7, computed there by the same
# function), kanana2's as PR 37 made it; kanana2's under bf16 is re-taken
# from PR 58's tree (latent attention's nine `split`s run in bfloat16 under
# the AMP pass: their raw vars and cast-backs are in the list), the other
# three did not move
SCOPED = {
    ("ouro", False): ("bfdc9a33a9492d67483c6f5267264b11f824829d", 421),
    ("ouro", True): ("8baa0cde1fdba6a009b7324ce94a57a105bb14ab", 630),
    ("kanana2", False): ("f27302c28b0eb18e1f2ce6cac7d226866550c306", 240),
    ("kanana2", True): ("a087a0f40c64cc5e6e02b6b0beda0b0dc0c67740", 405),
}


@pytest.mark.parametrize("model, use_bf16", sorted(SCOPED))
def test_a_scoped_builders_op_list_is_what_it_was(model, use_bf16):
    """Ouro's and kanana2's train programs, whose ops carry name scopes
    (ut<t>, exit; mla > down | up | rope | core | out, shared_expert):
    types, roles, variable names and scopes, in order.  A new attribute at
    its default (moe_ffn's, rotary_embed's, fused_attention's V width)
    leaves the digest alone."""
    build, hp = _scoped_lm_programs()[model]
    main, _, _, _ = build(hp, seq_len=16, lr=1e-3, use_bf16=use_bf16)
    assert [op.type for op in main.global_block().ops
            if "op_namescope" in op.attrs]
    assert _op_list_digest(main) == SCOPED[model, use_bf16]


@pytest.mark.parametrize("model, use_bf16", sorted(BEFORE))
def test_a_program_built_under_no_name_scope_is_what_it_was(model, use_bf16):
    """No op of GPT-2's, OLMoE's or LFM2's train program carries a name
    scope, its op list is the one the builders made before (types, roles,
    variable names, in order), and every part of every scope path in its
    lowered step names an op of the block, so the lowered text did not
    move."""
    build, hp = _lm_programs()[model]
    main, startup, _, fetches = build(hp, seq_len=16, lr=1e-3,
                                      use_bf16=use_bf16)
    assert not [op.type for b in range(main.num_blocks)
                for op in main.block(b).ops if "op_namescope" in op.attrs]
    assert _op_list_digest(main) == BEFORE[model, use_bf16]
    if use_bf16:
        return  # one lowering a model is enough for the paths
    from paddle_tpu.models import gpt2

    startup.random_seed = main.random_seed = 5
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=gpt2.make_fake_lm_batch(2, 16, hp, seed=1),
                fetch_list=[fetches[0]])
        (text,) = exe.compiled_hlo(main)
    ops = main.global_block().ops
    names = re.findall(r'op_name="jit\(program_step\)/([^"]*)"', text)
    assert len(names) > 50
    for op_name in names:
        found = SCOPE.findall(op_name)
        assert found, op_name
        # every part names an op of the block: none is a name scope's
        for role, typ, idx in found:
            assert (ops[int(idx)].type, ops[int(idx)].attrs["op_role"]) == (
                typ, role), op_name


def test_trinitys_attention_scopes_reach_the_lowered_steps_op_names():
    """attn_window / attn_full around a layer's attention, core around its
    fused_attention op, attn_gate around the gate's sigmoid and product,
    shared_expert: the nested part `<role>/<scopes joined by .>/<depth>`
    of the optimized HLO's op names carries each of them, forward and
    backward, under the op type the benchmark's readers match first
    (`[a-z]+/fused_attention(_grad)?/<i>` then `/attn_window.core/`)."""
    from paddle_tpu.models import gpt2, trinity

    class T(trinity.TrinityConfig):
        vocab_size, hidden_size, intermediate_size = 256, 64, 96
        moe_intermediate_size, num_hidden_layers, num_dense_layers = 32, 3, 1
        layer_types = ["sliding_attention", "full_attention",
                       "sliding_attention"]
        num_attention_heads, num_key_value_heads, head_dim = 4, 2, 32
        sliding_window, num_experts, num_experts_per_tok = 8, 8, 2

    main, startup, _, fetches = trinity.trinity_lm_program(T, seq_len=16,
                                                           lr=1e-3)
    startup.random_seed = main.random_seed = 5
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=gpt2.make_fake_lm_batch(2, 16, T, seed=1),
                fetch_list=[fetches[0]])
        (text,) = exe.compiled_hlo(main)
    ops, nested = main.global_block().ops, {}
    for op_name in re.findall(r'op_name="jit\(program_step\)/([^"]*)"', text):
        found = SCOPE.findall(op_name)
        want = ops[int(found[0][2])].attrs.get("op_namescope")
        if want is None:
            continue
        (role, typ, _), (_, scopes, depth) = found[:2]
        assert (scopes, int(depth)) == (want.replace("/", "."),
                                        want.count("/") + 1), op_name
        nested.setdefault(scopes, set()).add((role, typ))
    assert {"attn_window", "attn_window.core", "attn_window.attn_gate",
            "attn_full", "attn_full.core", "attn_full.attn_gate",
            "shared_expert"} <= set(nested)
    for kind in ("attn_window", "attn_full"):
        assert nested[kind + ".core"] == {
            ("forward", "fused_attention"),
            ("backward", "fused_attention_grad")}
        assert ("forward", "sigmoid") in nested[kind + ".attn_gate"]
        assert ("backward", "elementwise_mul_grad") in nested[
            kind + ".attn_gate"]
    assert nested["attn_window.rope"] == {
        ("forward", "rotary_embed"), ("backward", "rotary_embed_grad")}
    assert "attn_full.rope" not in nested
    assert not [t for _, t in nested["attn_full"] if "rotary" in t]


def test_kimi_linears_kda_scopes_reach_the_lowered_steps_op_names():
    """kda around a Kimi Delta Attention mixer with proj, conv, gate, core
    and out inside it: the nested part of the optimized HLO's op names
    carries each, forward and backward, under the op type the benchmark's
    readers match first (`[a-z]+/kda_attention(_grad)?/<i>` then
    `/kda.core/`); inside the op's lowering `intra` and `carry` follow; and
    the lowering leaves how it chunked the length in attribution()."""
    from paddle_tpu.models import gpt2, kimi_linear
    from paddle_tpu.ops import kernel_tuning

    class K(kimi_linear.KimiLinearConfig):
        vocab_size, hidden_size, intermediate_size = 256, 64, 96
        moe_intermediate_size, num_hidden_layers, kv_lora_rank = 32, 3, 32
        linear_attn_config = {"kda_layers": [1, 2], "full_attn_layers": [3],
                              "num_heads": 2, "head_dim": 16,
                              "short_conv_kernel_size": 4}
        num_attention_heads = num_key_value_heads = 2
        qk_nope_head_dim, qk_rope_head_dim, v_head_dim = 16, 8, 16
        num_experts, num_experts_per_token = 8, 2

    kernel_tuning.reset_attribution()
    main, startup, _, fetches = kimi_linear.kimi_linear_lm_program(
        K, seq_len=40, lr=1e-3)
    startup.random_seed = main.random_seed = 5
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=gpt2.make_fake_lm_batch(2, 40, K, seed=1),
                fetch_list=[fetches[0]])
        (text,) = exe.compiled_hlo(main)
    ops, nested, inside = main.global_block().ops, {}, set()
    for op_name in re.findall(r'op_name="jit\(program_step\)/([^"]*)"', text):
        found = SCOPE.findall(op_name)
        want = ops[int(found[0][2])].attrs.get("op_namescope")
        if want is None:
            continue
        (role, typ, _), (_, scopes, depth) = found[:2]
        assert (scopes, int(depth)) == (want.replace("/", "."),
                                        want.count("/") + 1), op_name
        nested.setdefault(scopes, set()).add((role, typ))
        if scopes == "kda.core":
            inside.update(re.findall(r"[/(](intra|carry)[/)]", op_name))
    assert {"kda.proj", "kda.conv", "kda.gate", "kda.core", "kda.out",
            "mla.rope", "mla.core", "shared_expert"} <= set(nested)
    assert nested["kda.core"] == {("forward", "kda_attention"),
                                  ("backward", "kda_attention_grad")}
    assert inside == {"intra", "carry"}
    assert {("forward", "causal_conv"), ("backward", "causal_conv_grad"),
            ("forward", "l2_normalize")} <= nested["kda.conv"]
    assert {("forward", "softplus"), ("forward", "exp"),
            ("forward", "sigmoid")} <= nested["kda.gate"]
    assert ("forward", "rms_norm") in nested["kda.out"]
    assert not [t for _, t in nested["mla.rope"] if "rotary" in t]
    chunks = kernel_tuning.attribution()["kda_chunks"]
    # two layers, each lowered forward and again inside its grad op
    assert chunks["ops"] == 4
    # ..., and the carry's kernels hold all B H = 4 heads a grid step
    assert chunks["lengths"] == {40: [64, 1, 40, 64, 4]}
    # a layer a direction: the carry walks forward in the forward op and
    # (traced, then dead) in the grad op's own forward, and the grad op's
    # backward walks forward once more for the entering states, then back
    hits = kernel_tuning.attribution()["pallas_hits"]
    assert (hits["kda_carry"], hits["kda_carry_bwd"]) == (6, 2)


def test_qwen3_nexts_gdn_and_rope_scopes_reach_the_lowered_steps_op_names():
    """gdn around a Gated DeltaNet mixer with proj, conv, gate, core and
    out inside it, attn_full around the gated attention with core,
    attn_gate and rope (the split, the rotation of the head's first lanes
    and the concatenation): the nested part of the optimized HLO's op names
    carries each, forward and backward, under the op type the benchmark's
    readers match first (`[a-z]+/gated_delta_attention(_grad)?/<i>` then
    `/gdn.core/`); inside the op's lowering `intra` and `carry` follow; and
    the lowering leaves how it chunked the length, and which decay it ran,
    in attribution()."""
    from paddle_tpu.models import gpt2, qwen3_next
    from paddle_tpu.ops import kernel_tuning

    class Q(qwen3_next.Qwen3NextConfig):
        vocab_size, hidden_size, num_hidden_layers = 256, 64, 4
        linear_num_key_heads, linear_num_value_heads = 2, 4
        linear_key_head_dim = linear_value_head_dim = 16
        num_attention_heads, num_key_value_heads, head_dim = 4, 2, 32
        moe_intermediate_size = shared_expert_intermediate_size = 32
        num_experts, num_experts_per_tok = 8, 2

    kernel_tuning.reset_attribution()
    main, startup, _, fetches = qwen3_next.qwen3_next_lm_program(
        Q, seq_len=40, lr=1e-3)
    startup.random_seed = main.random_seed = 5
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=gpt2.make_fake_lm_batch(2, 40, Q, seed=1),
                fetch_list=[fetches[0]])
        (text,) = exe.compiled_hlo(main)
    ops, nested, inside = main.global_block().ops, {}, set()
    for op_name in re.findall(r'op_name="jit\(program_step\)/([^"]*)"', text):
        found = SCOPE.findall(op_name)
        want = ops[int(found[0][2])].attrs.get("op_namescope")
        if want is None:
            continue
        (role, typ, _), (_, scopes, depth) = found[:2]
        assert (scopes, int(depth)) == (want.replace("/", "."),
                                        want.count("/") + 1), op_name
        nested.setdefault(scopes, set()).add((role, typ))
        if scopes == "gdn.core":
            inside.update(re.findall(r"[/(](intra|carry)[/)]", op_name))
    assert {"gdn.proj", "gdn.conv", "gdn.gate", "gdn.core", "gdn.out",
            "attn_full.core", "attn_full.rope", "attn_full.attn_gate",
            "shared_expert"} <= set(nested)
    assert nested["gdn.core"] == {
        ("forward", "gated_delta_attention"),
        ("backward", "gated_delta_attention_grad")}
    assert inside == {"intra", "carry"}
    assert {("forward", "causal_conv"), ("backward", "causal_conv_grad"),
            ("forward", "l2_normalize")} <= nested["gdn.conv"]
    assert {("forward", "softplus"), ("forward", "exp"),
            ("forward", "sigmoid")} <= nested["gdn.gate"]
    assert {("forward", "rms_norm"), ("forward", "swish")} <= nested[
        "gdn.out"]
    assert ("forward", "rotary_embed") in nested["attn_full.rope"]
    assert {t for _, t in nested["attn_full.core"]} == {
        "fused_attention", "fused_attention_grad"}
    found = kernel_tuning.attribution()
    # three layers, each lowered forward and again inside its grad op
    # (the last: the B Hv = 8 heads a grid step of the carry's kernels)
    assert found["gdn_chunks"] == {"ops": 6, "decay": "head",
                                   "lengths": {40: [64, 1, 40, 64, 8]}}
    # the carry is the family's one: three layers' walks count under KDA's
    # names, forward (op, grad op's dead forward, entering states) and back
    assert (found["pallas_hits"]["kda_carry"],
            found["pallas_hits"]["kda_carry_bwd"]) == (9, 3)
    assert found["kda_chunks"]["ops"] == 0


def test_nemotron_hs_mamba2_scopes_reach_the_lowered_steps_op_names():
    """mamba2 around a Mamba-2 mixer with in_proj, conv, core, norm and
    out_proj inside it: the nested part of the optimized HLO's op names
    carries each, forward and backward, under the op type the benchmark's
    readers match first (`[a-z]+/mamba2_scan(_grad)?/<i>` then
    `/mamba2.core/`); inside the op's lowering `chunk_scan` (the forward's
    kernel and the reverse walk) and `states` (the backward's first walk)
    tell the kernels apart; and every engagement counts under
    attribution()["pallas_hits"]["ssd"]."""
    from paddle_tpu.models import gpt2, nemotron_h
    from paddle_tpu.ops import kernel_tuning

    class N(nemotron_h.NemotronHConfig):
        vocab_size, hidden_size, num_hidden_layers = 256, 64, 4
        hybrid_override_pattern = "ME*M"
        mamba_num_heads, mamba_head_dim, n_groups, ssm_state_size = 4, 16, 2, 16
        num_attention_heads, num_key_value_heads, head_dim = 4, 2, 32
        moe_intermediate_size, moe_shared_expert_intermediate_size = 32, 64
        n_routed_experts, num_experts_per_tok = 8, 2

    kernel_tuning.reset_attribution()
    main, startup, _, fetches = nemotron_h.nemotron_h_lm_program(
        N, seq_len=40, lr=1e-3)
    startup.random_seed = main.random_seed = 5
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=gpt2.make_fake_lm_batch(2, 40, N, seed=1),
                fetch_list=[fetches[0]])
        (text,) = exe.compiled_hlo(main)
    ops, nested, inside = main.global_block().ops, {}, set()
    for op_name in re.findall(r'op_name="jit\(program_step\)/([^"]*)"', text):
        found = SCOPE.findall(op_name)
        want = ops[int(found[0][2])].attrs.get("op_namescope")
        if want is None:
            continue
        (role, typ, _), (_, scopes, depth) = found[:2]
        assert (scopes, int(depth)) == (want.replace("/", "."),
                                        want.count("/") + 1), op_name
        nested.setdefault(scopes, set()).add((role, typ))
        if scopes == "mamba2.core" and typ.startswith("mamba2_scan"):
            inside.update(re.findall(r"[/(](chunk_scan|states)[/)]", op_name))
    assert {"mamba2.in_proj", "mamba2.conv", "mamba2.core", "mamba2.norm",
            "mamba2.out_proj", "attn_full.core", "shared_expert"} <= set(
                nested)
    assert {("forward", "mamba2_scan"),
            ("backward", "mamba2_scan_grad")} <= nested["mamba2.core"]
    assert inside == {"chunk_scan", "states"}
    assert {("forward", "causal_conv"),
            ("backward", "causal_conv_grad")} <= nested["mamba2.conv"]
    assert ("forward", "rms_norm") in nested["mamba2.norm"]
    assert "attn_full.rope" not in nested  # position-free attention
    # two layers: the forward op's scan, and the grad op's three (its own
    # forward, traced and then dead, the states' walk and the reverse walk)
    assert kernel_tuning.attribution()["pallas_hits"]["ssd"] == 8
