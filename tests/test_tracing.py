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
# and differed in nothing but file names)
BEFORE = {
    ("gpt2", False): ("98754ed59062a5164c94a1b20a988527113db282", 132),
    ("gpt2", True): ("9c58fae632f1cd1321b1f1f8c1e5ee9daa5683b5", 216),
    ("olmoe", False): ("07649c6d3a776b64678eec1b24c7761f184934cd", 150),
    ("olmoe", True): ("ea273ac154b2fd554d4fc9e727a4741159455bb8", 232),
    ("lfm2", False): ("f38f922df3ec63949ba63a4c1bd662a6bd376e1b", 147),
    ("lfm2", True): ("5bd31a7f73c9c3fb42e91b3798ab0d4af2b998e8", 232),
}


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
