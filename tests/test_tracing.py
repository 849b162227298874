"""The one tracing system: names inside the compiled step
(core/trace.py's `<op_role>/<op type>/<index>` scopes in the optimized
HLO), RecordEvent on the device trace's clock (`paddle_tpu:<name>` on the
host plane of any running JAX trace) beside its chrome-trace list, the
Executor's spans (one set per run, the same from every run path), and
Executor.compiled_steps."""

import glob
import os
import re

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import layers, profiler

ROLES = ("forward", "backward", "optimize", "lrsched", "loss", "rpc")
SCOPE = re.compile(r"(?:^|[/(])(%s)/([\w.]+)/(\d+)(?=[/)]|$)"
                   % "|".join(ROLES))
INNER = ("feed_upload", "state_gather", "executor_run", "state_commit")


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


def _traced_runs(tmp_path_factory, path):
    """Run a train program five times under a plain jax.profiler trace
    (three steady steps, one with numpy fetches, one at a new batch size)
    through `path`; ([(compile_count rose, return_numpy)] per run, spans)."""
    from paddle_tpu.core import scope as scope_mod

    if path == "spmd":
        from paddle_tpu.models import gpt2
        from paddle_tpu.parallel import make_mesh

        class TinyHP(gpt2.GPT2Config):
            vocab_size, n_ctx, d_model, n_layer = 64, 16, 32, 1
            n_head, d_inner, dropout, tie_embeddings = 4, 64, 0.0, False

        old_main = fluid.framework.switch_main_program(fluid.Program())
        old_startup = fluid.framework.switch_startup_program(fluid.Program())
        try:
            main, startup, _, fetches = gpt2.gpt2_lm_program(
                TinyHP, seq_len=8, lr=3e-3,
                mesh=make_mesh({"dp": 1, "mp": 2},
                               devices=jax.devices()[:2]))
        finally:
            fluid.framework.switch_main_program(old_main)
            fluid.framework.switch_startup_program(old_startup)

        def feed(batch):
            return gpt2.make_fake_lm_batch(batch, 8, TinyHP, seed=0)
    else:
        main, startup, loss, _ = _small_train_program()
        fetches, feed = [loss], _feed

    trace_dir = str(tmp_path_factory.mktemp("trace_" + path))
    runs = []
    with fluid.scope_guard(scope_mod.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed(2), fetch_list=fetches)  # compile outside
        jax.profiler.start_trace(trace_dir)
        try:
            for batch, as_numpy in ((2, False), (2, False), (2, True),
                                    (4, False), (4, False)):
                before = exe.compile_count
                out = exe.run(main, feed=feed(batch), fetch_list=fetches,
                              return_numpy=as_numpy)
                runs.append((exe.compile_count > before, as_numpy))
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
    return runs, _host_spans(trace_dir)


@pytest.fixture(scope="module", params=["fast", "spmd"])
def traced(request, tmp_path_factory):
    if request.param == "spmd" and len(jax.devices()) < 2:
        pytest.skip("needs two virtual devices")
    runs, spans = _traced_runs(tmp_path_factory, request.param)
    return request.param, runs, spans


def _calls(spans):
    """[(outer span, [spans inside it in time])] per executor.run."""
    outer = [s for s in spans if s[2] == "executor.run"]
    return [(o, [s for s in spans if s is not o
                 and o[0] <= s[0] and s[1] <= o[1]]) for o in outer]


def test_every_run_emits_one_nested_set_of_spans(traced):
    path, runs, spans = traced
    calls = _calls(spans)
    assert len(calls) == len(runs)
    assert sum(len(inside) for _, inside in calls) + len(calls) \
        == len(spans), "a span outside every executor.run"
    for (outer, inside), (compiled, as_numpy) in zip(calls, runs):
        names = [s[2] for s in inside]
        for name in INNER:
            assert names.count(name) == 1, (path, names)
        assert names.count("fetch_to_host") == (1 if as_numpy else 0)
        # in the order the run goes through them
        order = [n for n in names if n in INNER]
        assert order == list(INNER), order
        # a first run at a signature takes the slow path; steady ones the
        # memoised one (the GSPMD path has one route for both)
        want = "spmd" if path == "spmd" else ("slow" if compiled else "fast")
        assert outer[3].get("path") == want, outer


def test_the_run_paths_emit_the_same_names(traced):
    _, runs, spans = traced
    names = {s[2] for s in spans}
    assert names == {"executor.run", "trace_compile", "fetch_to_host",
                     *INNER}


def test_trace_compile_exactly_when_compile_count_rises(traced):
    _, runs, spans = traced
    assert [c for c, _ in runs] == [False, False, False, True, False]
    for (outer, inside), (compiled, _) in zip(_calls(spans), runs):
        compiles = [s for s in inside if s[2] == "trace_compile"]
        assert bool(compiles) == compiled, (outer, compiles)
        for s in compiles:  # the cause is on the span
            assert "[4, " in s[3]["feed_sig"], s


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
