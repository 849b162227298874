"""A step statistic keeps its history: the Executor holds the last
STAT_WINDOW values of every persistable a step writes anew into a slot its
op's registration declares (moe_ffn's TokensPerExpert), each under the
step number of its `executor.run` span, and `Executor.step_stats` hands
them over without touching what a later step computes.  On a tiny program
of two expert layers that each hold experts [2, 4) of 8."""

import glob
import os

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor, framework, layers, unique_name
from paddle_tpu.core.registry import get_op
from paddle_tpu.param_attr import ParamAttr

B, T, D, F, E, K, HELD, OFFSET = 2, 8, 16, 8, 8, 2, 2, 2
STATS = ["moe_tokens_per_expert_0", "moe_tokens_per_expert_1"]
PATHS = ["flat", "spmd"]


def _program(train=True, stat_name="moe_tokens_per_expert", path="flat"):
    """x -> two residual expert layers -> a squared error against y; with
    `train`, SGD on it.  Parameters are named, so a second program built
    the same way shares them through the scope."""
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("x", shape=[B, T, D], append_batch_size=False)
        y = layers.data("y", shape=[B, T, D], append_batch_size=False)
        h = x
        for i in range(2):
            out, _, _ = layers.moe_ffn(
                h, E, F, K, norm_topk_prob=True, router="sigmoid",
                router_attr=ParamAttr(name="router%d" % i),
                expert_bias_attr=ParamAttr(name="bias%d" % i),
                gate_up_attr=ParamAttr(name="gate_up%d" % i),
                down_attr=ParamAttr(name="down%d" % i),
                num_local_experts=HELD, expert_offset=OFFSET,
                stat_name=stat_name)
            h = layers.elementwise_add(h, out)
        loss = layers.mean(layers.square_error_cost(h, y))
        if train:
            fluid.optimizer.SGD(0.5).minimize(loss)
    startup.random_seed = main.random_seed = 7
    if path == "spmd":
        from paddle_tpu.parallel import annotate_spmd, make_mesh
        from paddle_tpu.parallel.partition_rules import PartitionRules

        annotate_spmd(main, make_mesh({"dp": 1, "mp": 2},
                                      devices=jax.devices()[:2]),
                      PartitionRules())
    return main, startup, loss


def _feed(i):
    rng = np.random.RandomState(100 + i)
    return {"x": rng.randn(B, T, D).astype("float32"),
            "y": rng.randn(B, T, D).astype("float32")}


def _train(path, n, read_at=(), fetch_stats=False):
    """n steps of a fresh program in a fresh scope on a fresh Executor:
    (exe, main, losses, parameters after the last step, what each step
    fetched of STATS, the step_stats taken after the steps in `read_at`)."""
    if path == "spmd" and len(jax.devices()) < 2:
        pytest.skip("needs two virtual devices")
    main, startup, loss = _program(path=path)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    losses, fetched, read = [], [], []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for i in range(n):
            out = exe.run(main, feed=_feed(i),
                          fetch_list=[loss] + (STATS if fetch_stats else []))
            losses.append(np.asarray(out[0]))
            fetched.append([np.asarray(v) for v in out[1:]])
            if i in read_at:
                read.append(exe.step_stats(main))
        params = {p.name: np.asarray(scope.find_var(p.name))
                  for p in main.global_block().all_parameters()}
    return exe, main, losses, params, fetched, read


def test_moe_ffn_declares_its_statistic_and_nothing_else_does():
    assert get_op("moe_ffn").stat_outputs == ("TokensPerExpert",)
    assert get_op("expert_bias_update").stat_outputs == ()
    assert get_op("mul").stat_outputs == ()


@pytest.mark.parametrize("path", PATHS)
def test_n_steps_give_n_entries_equal_to_what_each_step_fetched(path):
    """The history against a SECOND, identical run that fetched the
    statistic step by step: row for row, under consecutive numbers."""
    n = 5
    _, _, _, _, fetched, _ = _train(path, n, fetch_stats=True)
    exe, main, _, _, _, _ = _train(path, n)
    kept = exe.step_stats(main)
    assert sorted(kept) == STATS
    for j, name in enumerate(STATS):
        steps, values = kept[name]
        assert steps.dtype == np.int64 and values.dtype == np.int32
        # the startup program took number 0
        assert steps.tolist() == list(range(1, n + 1))
        assert values.shape == (n, E)
        assert np.array_equal(values, np.stack([f[j] for f in fetched]))
        assert (values.sum(axis=1) == B * T * K).all()  # dropless
    # not one step's counts n times over: the router moved
    assert len({kept[STATS[0]][1][i].tobytes() for i in range(n)}) > 1


@pytest.mark.parametrize("path", PATHS)
def test_reading_the_history_changes_nothing_a_later_step_computes(path):
    """Losses and every parameter after six steps, bit for bit, with and
    without step_stats calls in between; reading does not clear."""
    _, _, losses, params, _, _ = _train(path, 6)
    _, _, losses2, params2, _, read = _train(path, 6, read_at=(2, 3))
    assert all(np.array_equal(a, b) for a, b in zip(losses, losses2))
    assert sorted(params) == sorted(params2) and len(params) == 8
    for name in params:
        assert np.array_equal(params[name], params2[name]), name
    early, late = read
    assert len(early[STATS[0]][0]) == 3 and len(late[STATS[0]][0]) == 4
    assert np.array_equal(late[STATS[0]][1][:3], early[STATS[0]][1])


def test_the_ring_stops_at_its_window_and_keeps_the_newest():
    assert executor.STAT_WINDOW == 256
    n = executor.STAT_WINDOW + 4
    exe, main, _, _, fetched, _ = _train("flat", n, fetch_stats=True)
    steps, values = exe.step_stats(main)[STATS[1]]
    assert steps.tolist() == list(range(5, n + 1))
    assert np.array_equal(values, np.stack([f[1] for f in fetched[4:]]))


def _run_spans(trace_dir):
    """The stats of every paddle_tpu:executor.run span, in start order."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            spans += [(e.start_ns, dict(e.stats)) for e in line.events
                      if e.name == "paddle_tpu:executor.run"]
    return [stats for _, stats in sorted(spans, key=lambda s: s[0])]


def test_an_entrys_number_is_its_run_spans_step(tmp_path):
    """A traced slice's spans and the ring's entries name each other; a
    run of another program in between takes a number and leaves a gap."""
    main, startup, loss = _program()
    fwd, _, fwd_loss = _program(train=False, stat_name="moe_eval")
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(0), fetch_list=[loss])
        exe.run(fwd, feed=_feed(0), fetch_list=[fwd_loss])
        jax.profiler.start_trace(str(tmp_path))
        try:
            for i in range(3):
                exe.run(main, feed=_feed(i), fetch_list=[loss])
            exe.run(fwd, feed=_feed(0), fetch_list=[fwd_loss])
            out = exe.run(main, feed=_feed(3), fetch_list=[loss],
                          return_numpy=False)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
    spans = _run_spans(str(tmp_path))
    assert [s["path"] for s in spans] == ["fast"] * 5
    assert [int(s["step"]) for s in spans] == [3, 4, 5, 6, 7]
    assert exe.step_stats(main)[STATS[0]][0].tolist() == [1, 3, 4, 5, 7]
    assert exe.step_stats(fwd)["moe_eval_0"][0].tolist() == [2, 6]


def test_a_forward_program_sharing_the_scope_fills_a_ring_of_its_own():
    main, startup, loss = _program()
    fwd, _, fwd_loss = _program(train=False, stat_name="moe_eval")
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for i in range(3):
            exe.run(main, feed=_feed(i), fetch_list=[loss])
        before = exe.step_stats(main)
        exe.run(fwd, feed=_feed(2), fetch_list=[fwd_loss])
        after, own = exe.step_stats(main), exe.step_stats(fwd)
        in_scope = np.asarray(scope.find_var("moe_eval_1"))
    assert sorted(own) == ["moe_eval_0", "moe_eval_1"]
    assert own["moe_eval_1"][0].tolist() == [4]
    assert np.array_equal(own["moe_eval_1"][1][0], in_scope)
    for name in STATS:
        assert np.array_equal(before[name][0], after[name][0])
        assert np.array_equal(before[name][1], after[name][1])
    exe.close()
    assert exe.step_stats(main) == {} and exe.step_stats(fwd) == {}


def test_a_statistic_the_step_reads_first_is_donated_and_keeps_no_history():
    """An accumulator: the step reads the counts the last step left before
    it writes its own, so the variable is read-write state, donated to
    the next step; holding its array would be holding a deleted one."""
    main, startup, loss = _program()
    block = main.global_block()
    seen = block.create_var(name="counts_seen", shape=[E], dtype="int32",
                            persistable=True)
    block.prepend_op("assign", {"X": [block.var(STATS[0])]},
                     {"Out": [seen]}, {})
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        scope.set(STATS[0], np.zeros([E], "int32"))
        for i in range(3):
            exe.run(main, feed=_feed(i), fetch_list=[loss])
            kept = exe.step_stats(main)  # never a deleted array
        last = np.asarray(scope.find_var(STATS[0]))
    (step,) = exe.compiled_steps(main)
    traced = exe._cache.blocks_for(main)[0].traced
    assert STATS[0] in traced.rw_names and step.path == "flat"
    assert traced.stat_names == (STATS[1],)
    assert sorted(kept) == [STATS[1]] and len(kept[STATS[1]][0]) == 3
    assert last.sum() == B * T * K


def test_a_program_without_a_statistic_returns_nothing_and_keeps_nothing():
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        loss = layers.mean(layers.fc(x, size=1))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(2):
            exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                    fetch_list=[loss])
    assert exe.step_stats(main) == {} and exe.step_stats(startup) == {}
    assert len(exe._stat_rings) == 0
    assert exe._cache.blocks_for(main)[0].traced.stat_names == ()
