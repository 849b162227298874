"""The donated state lives in the layout the compiled step reads it in
(core/trace.jit_step, CompiledBlock): a step is compiled ahead of its first
call with the layout of every read-write array left to the compiler, the
scope's arrays are laid out once in what it chose, and the results come
back so.  The CPU's compiler answers the default layout for everything, so
these tests put a column-major layout on chosen arrays IN THE TEST (the
program has no such option: `state_format` is patched here) and hold every
value against the run that lays nothing out."""

import warnings

import jax
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.core import trace

W, MOMENTS = "fc_0.w_0", ("fc_0.w_0_moment1_0", "fc_0.w_0_moment2_0")


def _program():
    """A two-layer classifier under Adam: matrices, vectors and [1]
    accumulators among its read-write state, a [1] loss as its fetch."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[12])
        y = fluid.layers.data("y", shape=[1], dtype="int64")
        hidden = fluid.layers.fc(x, 16, act="relu")
        pred = fluid.layers.fc(hidden, 4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, y))
        test = main.clone(for_test=True)
        fluid.optimizer.Adam(0.01).minimize(loss)
    return main, startup, test, loss


def _batch(i):
    rng = np.random.RandomState(i)
    return {"x": rng.rand(8, 12).astype("float32"),
            "y": rng.randint(0, 4, (8, 1)).astype("int64")}


def _force(monkeypatch, program, names):
    """Every read-write array in a concrete layout: column-major for
    `names`, the default for the rest (XLA's CPU compiler takes concrete
    layouts or its own, not a mix)."""
    def state_format(name, sharding):
        ndim = len(program.global_block()._find_var_recursive(name).shape)
        order = tuple(range(ndim))
        return Format(Layout(order[::-1] if name in names else order, ()),
                      sharding)

    monkeypatch.setattr(trace, "state_format", state_format)


def _train(steps=2, scope=None, exe=None, programs=None, first=0):
    main, startup, test, loss = programs or _program()
    exe = exe or fluid.Executor(fluid.CPUPlace())
    if scope is None:
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
    with fluid.scope_guard(scope):
        losses = [exe.run(main, feed=_batch(i), fetch_list=[loss])[0]
                  for i in range(first, first + steps)]
    return losses, scope, exe, (main, startup, test, loss)


def _state(scope, program):
    return {v.name: np.asarray(scope.find_var(v.name))
            for v in program.list_vars()
            if v.persistable and scope.find_var(v.name) is not None}


def _equal(got, want):
    assert sorted(got) == sorted(want)
    for n in want:
        np.testing.assert_array_equal(got[n], want[n], err_msg=n)


def _records(program):
    return [r["args"] for r in profiler.phases()
            if r["name"] == "trace_compile"
            and r["args"].get("program") == id(program)]


def _layout(array):
    return array.format.layout.major_to_minor


@pytest.fixture(scope="module")
def plain():
    """Two steps with no format asked for: the jit as it was."""
    saved = trace.state_format
    trace.state_format = lambda name, sharding: None
    try:
        losses, scope, _exe, programs = _train()
        return losses, _state(scope, programs[0])
    finally:
        trace.state_format = saved


@pytest.mark.parametrize("forced", [
    (), (W,), (W,) + MOMENTS, ("fc_1.w_0",) + MOMENTS, "compiler"],
    ids=["default", "weight", "weight+moments", "mixed", "compiler"])
def test_a_layout_changes_no_value_and_is_counted(monkeypatch, plain, forced):
    """Loss and every persistable after two steps are bit-equal to the
    run that asked for no format, whichever arrays are laid out otherwise;
    the arrays live in the scope in the layout asked for, and the step's
    trace_compile record counts the one-time moves."""
    programs = _program()
    if forced != "compiler":
        _force(monkeypatch, programs[0], forced)
    losses, scope, exe, _ = _train(programs=programs)
    want_losses, want_state = plain
    np.testing.assert_array_equal(losses, want_losses)
    _equal(_state(scope, programs[0]), want_state)
    moved = () if forced == "compiler" else forced
    for name in (W, "fc_1.w_0") + MOMENTS:
        assert _layout(scope.find_var(name)) == (
            (1, 0) if name in moved else (0, 1))
    (record,) = _records(programs[0])
    assert record["state_relayouts"] == len(moved)
    assert record["state_relayout_s"] >= 0.0
    (startup_record,) = _records(programs[1])
    assert startup_record["state_relayouts"] == 0
    assert [b.compiles for b in exe._cache.blocks_for(programs[0])] == [1]


@pytest.mark.parametrize("forced", [(), (W,) + MOMENTS],
                         ids=["default", "laid-out"])
def test_the_state_is_still_donated(monkeypatch, forced):
    """Every read-write array a step took is deleted when it returns (its
    buffer is the result's), and JAX warns of no donated buffer it could
    not use: on the first step, which lays arrays out (the step takes the
    copy, and the scope lets go of the array as it came), and on the
    second, which takes the first's results as they came."""
    programs = _program()
    _force(monkeypatch, programs[0], forced)
    _, scope, exe, _ = _train(steps=0, programs=programs)
    rw_names = None
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for i in range(2):
            if rw_names is None:  # before a block exists: every persistable
                held = {n: scope.find_var(n) for n in scope.local_var_names()}
            else:
                held = {n: scope.find_var(n) for n in rw_names}
            _train(steps=1, scope=scope, exe=exe, programs=programs, first=i)
            (block,) = exe._cache.blocks_for(programs[0])
            rw_names = block.traced.rw_names
            assert len(rw_names) > 10
            moved = forced if i == 0 else ()
            assert all(held[n].is_deleted() for n in rw_names
                       if n not in moved)
            assert not any(scope.find_var(n).is_deleted() for n in rw_names)


@pytest.mark.parametrize("first", ["eval", "train"])
def test_a_second_program_reads_the_laid_out_parameters(monkeypatch, plain,
                                                        first):
    """The test Program takes the parameters as read-only state.  Compiled
    after the train step laid them out, it is compiled for the layout they
    arrive in, as a jit with no layout given would be; compiled before, it
    is refused the laid-out arrays by JAX, compiles once more for what
    arrives, and stays there.  Either way it agrees with itself over the
    default layout, and the training goes on as if nobody had looked."""
    programs = _program()
    main, _startup, test, loss = programs
    _force(monkeypatch, main, (W,) + MOMENTS)
    _, scope, exe, _ = _train(steps=0, programs=programs)

    def evaluate():
        with fluid.scope_guard(scope):
            return exe.run(test, feed=_batch(9), fetch_list=[loss])[0]

    before = evaluate() if first == "eval" else None
    _train(steps=1, scope=scope, exe=exe, programs=programs)
    assert _layout(scope.find_var(W)) == (1, 0)
    after = [evaluate(), evaluate()]
    (block,) = exe._cache.blocks_for(test)
    assert block.compiles == (2 if first == "eval" else 1)
    assert block.executable.input_formats[0][1][W] == scope.find_var(W).format
    assert len(_records(test)) == block.compiles
    np.testing.assert_array_equal(after[0], after[1])

    reference = _program()
    trace_format = trace.state_format
    monkeypatch.setattr(trace, "state_format", lambda name, sharding: None)
    _, ref_scope, ref_exe, _ = _train(steps=0, programs=reference)
    with fluid.scope_guard(ref_scope):
        if before is not None:
            np.testing.assert_array_equal(before, ref_exe.run(
                reference[2], feed=_batch(9), fetch_list=[reference[3]])[0])
        _train(steps=1, scope=ref_scope, exe=ref_exe, programs=reference)
        np.testing.assert_array_equal(after[0], ref_exe.run(
            reference[2], feed=_batch(9), fetch_list=[reference[3]])[0])
    monkeypatch.setattr(trace, "state_format", trace_format)
    losses, _, _, _ = _train(steps=1, scope=scope, exe=exe,
                             programs=programs, first=1)
    np.testing.assert_array_equal(losses[0], plain[0][1])
    _equal(_state(scope, main), plain[1])


@pytest.mark.parametrize("forced", [(), (W,) + MOMENTS],
                         ids=["default", "laid-out"])
def test_save_load_step_round_trips(monkeypatch, plain, tmp_path, forced):
    """A checkpoint holds values, not layouts: saved after one step from a
    scope whose arrays are laid out, loaded (host arrays, so the default
    layout), the second step lays them out again, counts that, compiles
    nothing, and ends where the uninterrupted run ends."""
    programs = _program()
    main = programs[0]
    _force(monkeypatch, main, forced)
    _, scope, exe, _ = _train(steps=1, programs=programs)
    with fluid.scope_guard(scope):
        fluid.io.save_persistables(exe, str(tmp_path), main)
        saved = _state(scope, main)
        for n in saved:
            scope.set(n, np.zeros_like(saved[n]))
        fluid.io.load_persistables(exe, str(tmp_path), main)
    _equal(_state(scope, main), saved)
    losses, _, _, _ = _train(steps=1, scope=scope, exe=exe,
                             programs=programs, first=1)
    np.testing.assert_array_equal(losses[0], plain[0][1])
    _equal(_state(scope, main), plain[1])
    (record,) = _records(main)
    assert record["state_relayouts"] == 2 * len(forced)
    (block,) = exe._cache.blocks_for(main)
    assert block.compiles == 1 and exe.compile_count == 2
    for name in forced:
        assert _layout(scope.find_var(name)) == (1, 0)


@pytest.mark.parametrize("forced", [(), (W,)], ids=["default", "laid-out"])
def test_compiled_hlo_is_the_text_of_the_executable_that_ran(monkeypatch,
                                                             forced):
    """compiled_hlo lowers nothing again: it hands out the text of the
    executable the block runs, whose entry parameter of a laid-out array
    carries the layout it was laid out in."""
    programs = _program()
    _force(monkeypatch, programs[0], forced)
    _, _, exe, _ = _train(steps=1, programs=programs)
    (block,) = exe._cache.blocks_for(programs[0])
    counted, n_phases = profiler.counters(), len(profiler.phases())
    (text,) = exe.compiled_hlo(programs[0])
    assert text == block.executable.as_text()
    # nothing was traced, lowered or compiled for it
    assert profiler.counters() == counted
    assert len(profiler.phases()) == n_phases
    assert text.startswith("HloModule jit_program_step")
    entry = text[text.index("\nENTRY "):]
    weight = [ln for ln in entry.splitlines()
              if "parameter(" in ln and "%rw_state__fc_0_w_0__." in ln]
    assert len(weight) == 1
    assert ("f32[12,16]{0,1}" in weight[0]) == bool(forced)
    assert ("f32[12,16]{1,0}" in weight[0]) == (not forced)


class _Traced:
    """A hand-made traced step: `a` keeps its shape, `b` comes back twice
    as long (its buffer cannot be the result's), the fetch has `a`'s shape
    and dtype (JAX would pair it with the donated `a` were it first)."""
    ro_names, rw_names = ["r"], ["a", "b"]
    updated, fetch_names = ["a", "b", "c"], ["out"]

    @staticmethod
    def fn(feeds, ro_state, rw_state, rng_key):
        a, b = rw_state["a"], rw_state["b"]
        return [a * ro_state["r"]], {
            "a": a + feeds["x"], "b": jax.numpy.concatenate([b, b]),
            "c": a - 1.0}


def test_a_result_of_another_shape_does_not_take_the_buffer():
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    four = jax.ShapeDtypeStruct((4,), "float32", sharding=sharding)
    jitted = trace.jit_step(_Traced, {"a": sharding, "b": sharding})
    compiled = jitted.lower({"x": four}, {"r": four}, {"a": four, "b": four},
                            four).compile()
    ones = jax.device_put(np.ones(4, "float32"), sharding)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        kept, fetches, fresh = compiled(
            {"x": ones}, {"r": ones}, {"a": ones + 1, "b": ones + 2}, ones)
    assert sorted(kept) == ["a", "b"] and sorted(fresh) == ["b", "c"]
    np.testing.assert_array_equal(kept["a"], 3 * np.ones(4))
    np.testing.assert_array_equal(kept["b"], 3 * np.ones(4))  # as it came
    np.testing.assert_array_equal(fresh["b"], 3 * np.ones(8))
    np.testing.assert_array_equal(fetches[0], 2 * np.ones(4))


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compilation cache on, in a directory of this
    test's (a CPU process has none: compile_cache.py), every compile
    written; as it was afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (str(tmp_path), 0.0, 0)):
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    events = []
    listening = [True]
    jax.monitoring.register_event_listener(
        lambda event, **kw: listening[0] and events.append(
            event.rsplit("/", 1)[-1]))
    try:
        yield events
    finally:
        listening[0] = False
        for n, v in before.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()
        jax.clear_caches()


@pytest.mark.parametrize("forced", [(), (W,) + MOMENTS],
                         ids=["default", "laid-out"])
def test_a_step_read_from_the_persistent_cache_keeps_the_layouts(
        monkeypatch, plain, persistent_cache, forced):
    """Every process but the first reads its executables from the
    persistent cache, and JAX 0.9.0 hands the results of such an
    executable out under the default layout whatever layout it wrote them
    in (trace.relabelled).  A second run of the same program with
    everything this process compiled forgotten reads the step (and the
    identities that lay the arrays out) from the cache, writes nothing to
    it, and trains to the same bits, its arrays in the scope under the
    layout they are in; so does an evaluation that reads them."""
    ran = []
    for _ in range(2):
        jax.clear_caches()
        del persistent_cache[:]
        programs = _program()
        _force(monkeypatch, programs[0], forced)
        losses, scope, exe, _ = _train(programs=programs)
        with fluid.scope_guard(scope):
            cost = exe.run(programs[2], feed=_batch(9),
                           fetch_list=[programs[3]])[0]
        (block,) = exe._cache.blocks_for(programs[0])
        ran.append((persistent_cache.count("cache_hits"),
                    persistent_cache.count("cache_misses"),
                    sorted(block.mislabelled), cost))
        np.testing.assert_array_equal(losses, plain[0])
        _equal(_state(scope, programs[0]), plain[1])
        for name in (W,) + MOMENTS:
            assert _layout(scope.find_var(name)) == (
                (1, 0) if name in forced else (0, 1))
    (_, written, labels, cost), (read, written_again, labels_again,
                                 cost_again) = ran
    assert written >= 3 and read >= 3 and written_again == 0
    assert labels == [] and labels_again in ([], sorted(forced))
    np.testing.assert_array_equal(cost, cost_again)


def _cell(name):
    """(cfg, work, adapter) of a benchmark cell at its rehearsal's widths."""
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchmark_run_for_state_layout",
        os.path.join(root, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    registry = run.load_json(root, "BENCHMARK.json")
    cell = run.find(registry["workloads"], name, "workload")
    cfg = run.merged(run.load_json(root, run.find(
        registry["configs"], cell["config"], "config")["file"]), True)
    work = run.merged(run.load_json(
        run.BENCH_DIR, "workloads", name + ".json"), True)
    return cfg, work, run.load_module("adapters", cfg["adapter"])


@pytest.mark.parametrize("cell, turned", [
    ("nemotron3_nano_30b_a3b_train", "moe_up.w"),
    ("gpt2_345m_train", "ffn_in"),
])
def test_a_cell_trains_to_the_same_bits_at_its_rehearsal_widths(
        monkeypatch, cell, turned):
    """Two steps of a benchmark cell's own train Program on the CPU, at
    the rehearsal's widths: with the layouts left to the compiler, and
    with the arrays whose name holds `turned` (Nemotron's up-projection
    experts and their moments, the arrays the chip's compiler lays out
    otherwise; GPT-2's first FFN matrices) put minor-to-major HERE, loss
    and every persistable are equal, bit for bit, to the run that asks for
    no format."""
    cfg, work, adapter = _cell(cell)

    def train(state_format):
        with fluid.unique_name.guard():
            built = adapter.build(cfg, work)
        main, startup = built["main"], built["startup"]
        main.random_seed = startup.random_seed = 11
        if state_format == "turned":
            block = main.global_block()

            def state_format(name, sharding):
                order = tuple(range(len(
                    block._find_var_recursive(name).shape)))
                return Format(Layout(
                    order[::-1] if turned in name and len(order) > 1
                    else order, ()), sharding)

        if state_format is not None:
            monkeypatch.setattr(trace, "state_format", state_format)
        scope, exe = fluid.Scope(), fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            exe.run(startup)
            losses = [exe.run(main, feed=adapter.make_batch(cfg, work, i),
                              fetch_list=[built["loss"]])[0]
                      for i in range(2)]
        monkeypatch.undo()
        moved = [r["args"]["state_relayouts"] for r in profiler.phases()
                 if r["name"] == "trace_compile"
                 and r["args"].get("program") == id(main)]
        return losses, _state(scope, main), moved

    want_losses, want_state, _ = train(lambda name, sharding: None)
    for state_format in (None, "turned"):
        losses, state, (moved,) = train(state_format)
        np.testing.assert_array_equal(losses, want_losses)
        _equal(state, want_state)
        assert (moved > 0) == (state_format == "turned")
