"""readers/program_profile.py (and the two readers over it) against a
small trace and HLO whose numbers are computed by hand.

data/profile_trace.pbtxt (times in microseconds): device 0 runs
jit_program_step three times, [0,100] [200,300] [400,500]; the steady
window leaves out the first: [200,500], 2 steps, each

  fusion.1            [+0,+20]   kLoop; root scoped forward/relu/1
  fusion.2            [+20,+60]  kOutput; holds a convolution scoped
                                 backward/mul_grad/5 and Adam (optimize/
                                 adam/9): its own op_name and its root's
                                 operands say adam, the matmul rule says
                                 mul_grad
  all-reduce-start.1  [+60,+62]  in flight until all-reduce-done.1
  fusion.3            [+65,+75]  kLoop, backward/relu_grad/4: covers half of
                                 the collective
  all-reduce-done.1   [+78,+80]  no metadata of its own; its -start is
                                 scoped backward/c_allreduce_sum/6
  copy.7              [+80,+90]  not in the HLO at all
  fusion.4            [+90,+100] no metadata on the fusion; root scoped
                                 optimize/adam/10

so a step is busy 100 us: forward 20, backward 40 + 10 + the collective's
exposed 10 = 60, optimize 10, unattributed 10.  Idle is [300,400].  Host
plane: executor.run [150,190] holding feed_upload 4, state_gather 10,
executor_run 12, state_commit 6 us (8 us under no child), and
executor.run [305,395] holding 6, 14, 60 ([330,390]), 3 us; so state is
16 and 17 us, dispatch 12 and 60 us, and 90 of the 100 idle microseconds
lie under an executor.run, 60 of them under its executor_run.
"""

import os

import pytest

from conftest import BENCH_DIR, CELLS, RUN, SPEC

DATA = os.path.join(BENCH_DIR, "tests", "data")
NEW = ["fwd_time_share", "bwd_time_share", "opt_time_share",
       "unattributed_time_share", "exposed_collective_share",
       "exe_state_ms", "exe_dispatch_ms", "idle_in_executor_share"]


@pytest.fixture(scope="module")
def pp():
    return RUN.load_module("readers", "program_profile")


@pytest.fixture(scope="module")
def prof(pp):
    from jax.profiler import ProfileData

    tr = RUN.load_module("", "trace_reduce")
    with open(os.path.join(DATA, "profile_trace.pbtxt")) as f:
        data = ProfileData.from_text_proto(f.read())
    with open(os.path.join(DATA, "profile_hlo.txt")) as f:
        return pp.reduce_profile(data, [f.read()], tr)


def _read(metric, ctx):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    return RUN.load_module("readers", how["reader"]).read(
        ctx, **how.get("args", {}))


def test_window_and_owned_time(prof):
    assert prof["steps"] == 2
    assert prof["window_ms"] == pytest.approx(0.300)
    assert prof["busy_ms"] == pytest.approx(0.200)
    assert prof["slice_step_ms"] == pytest.approx(0.150)
    assert sum(prof["scope_ms"].values()) == pytest.approx(prof["busy_ms"])
    assert prof["scope_ms"] == {
        "forward/relu/1": pytest.approx(0.040),
        "backward/mul_grad/5": pytest.approx(0.080),
        "backward/relu_grad/4": pytest.approx(0.020),
        "backward/c_allreduce_sum/6": pytest.approx(0.020),
        "": pytest.approx(0.020),
        "optimize/adam/10": pytest.approx(0.020)}


@pytest.mark.parametrize("metric, value", [
    ("fwd_time_share", 20.0), ("bwd_time_share", 60.0),
    ("opt_time_share", 10.0), ("unattributed_time_share", 10.0),
    ("exposed_collective_share", 10.0),
    ("exe_state_ms", 0.0165), ("exe_dispatch_ms", 0.036),
    ("idle_in_executor_share", 90.0)])
def test_each_new_metric_by_hand(prof, metric, value):
    ctx = {"program_profile": prof, "load_module": RUN.load_module}
    assert _read(metric, ctx) == pytest.approx(value)


def test_the_four_time_shares_sum_to_100(prof):
    ctx = {"program_profile": prof, "load_module": RUN.load_module}
    assert sum(_read(m, ctx) for m in NEW[:4]) == pytest.approx(100.0)


def test_a_fusion_goes_to_the_matmul_inside_it(pp, prof):
    ops = dict(prof["device_ops"])
    ns, opcode, kind, scope, scopes = ops["%fusion.2"]
    assert (opcode, kind, scope) == ("fusion", "kOutput",
                                     "backward/mul_grad/5")
    assert scopes == {"backward/mul_grad/5", "optimize/adam/9"}
    # fusion.2 is the one op whose members span two roles: 80 of 200 us
    assert prof["mixed_role_share"] == pytest.approx(40.0)
    # without a matmul: the root's scope, and -done takes its -start's
    assert ops["%fusion.4"][3] == "optimize/adam/10"
    assert ops["%all-reduce-start.1"][3] == "backward/c_allreduce_sum/6"
    assert "%all-reduce-done.1" not in ops  # one interval with its -start
    assert ops["%copy.7"][3] == "" and prof["unjoined_ops"] == 1


def test_scopes_nest_and_only_fluid_scopes_count(pp):
    assert pp.scope_path(
        "jit(program_step)/forward/while/5/while/body/forward/mul/2/dot"
    ) == "forward/while/5/forward/mul/2"
    assert pp.scope_path(
        "jit(program_step)/backward/mul_grad/7/transpose(jvp(forward/"
        "recompute/3))/mul") == "backward/mul_grad/7/forward/recompute/3"
    assert pp.scope_path("jit(program_step)/jit(_where)/select_n") == ""
    assert pp.scope_path("jit(f)/layer/attn/0/dot_general") == ""


def test_calls_and_idle_gaps_carry_the_programs_spans(prof):
    first, second = prof["calls"]
    assert first == {
        "executor.run": pytest.approx(0.040),
        "feed_upload": pytest.approx(0.004),
        "state_gather": pytest.approx(0.010),
        "executor_run": pytest.approx(0.012),
        "state_commit": pytest.approx(0.006),
        "other": pytest.approx(0.008)}
    assert second["executor_run"] == pytest.approx(0.060)
    # the one gap [300,400]: executor.run covers 90 us of it, the
    # executor_run inside it 60 us, over half: the innermost owner
    assert prof["idle_gaps"] == [[
        "paddle_tpu:executor_run", pytest.approx(0.100),
        {"executor.run": pytest.approx(0.090),
         "feed_upload": pytest.approx(0.006),
         "state_gather": pytest.approx(0.014),
         "executor_run": pytest.approx(0.060),
         "state_commit": pytest.approx(0.003),
         "caller": pytest.approx(0.010)}]]


def test_the_summary_line_is_json_and_names_op_types(pp, prof):
    import json

    line = json.loads(json.dumps(pp._summary(prof)))
    assert line["role_share"] == {
        "forward": pytest.approx(20.0), "backward": pytest.approx(60.0),
        "optimize": pytest.approx(10.0), "unattributed": pytest.approx(10.0)}
    assert line["op_types_ms_per_step"][0] == [
        "backward/mul_grad", pytest.approx(0.040), pytest.approx(40.0)]
    top = line["device_ops_ms_per_step"][0]
    assert top[0] == "%fusion.2 fusion kOutput"
    assert top[2:] == ["backward/mul_grad/5",
                       ["backward/mul_grad x1", "optimize/adam x1"]]
    assert line["unattributed_ms_per_step"] == [
        ["copy for ?", pytest.approx(0.010)]]
    assert line["span_ms_per_call"]["state_gather"] == pytest.approx(0.012)


def test_owned_time_gives_an_instant_to_the_op_that_started_last(pp):
    # a collective [0,10] under compute [2,5] and [4,8]: the collective
    # keeps [0,2] and [8,10]; the second compute op takes over at 4
    assert pp.owned_time([(0, 10, True), (2, 5, False), (4, 8, False)]) \
        == [4.0, 2.0, 4.0]


@pytest.mark.parametrize("cell", CELLS)
def test_without_the_programs_names_every_new_metric_is_left_out(cell):
    """A program from before the scopes and spans (no compiled_steps), or
    a run with no device plane (a rehearsal): the readers return None and
    do not raise, so the line leaves the metrics out."""
    logged = []
    ctx = {"exe": object(), "main": object(), "log": logged.append,
           "load_module": RUN.load_module}
    reported = [m["name"] for m in RUN.cell_metrics(SPEC["per_layer"], cell)]
    mine = [m for m in NEW if m in reported]
    assert len(mine) == (8 if cell == "gpt2_345m_train_dp2mp2" else 7)
    assert [_read(m, ctx) for m in mine] == [None] * len(mine)
    assert logged == []


def test_the_new_entries_are_appended_data_over_existing_protocol():
    names = [m["name"] for m in SPEC["per_layer"]]
    assert names[-8:] == NEW
    for m in SPEC["per_layer"][-8:]:
        assert m["source"] == "device_trace" and m["better"] == "lower"
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", m["name"] + ".json"))
