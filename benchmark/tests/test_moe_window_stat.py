"""The router's metrics over the steps a run kept (readers/
moe_window_stat.py, from Executor.step_stats): the five values on a
hand-made history by hand arithmetic, what the reader leaves out and when,
the registry entries found by name, and the real command's rehearsal line
with the metrics on it."""

import json
import types

import numpy as np
import pytest

from conftest import BENCH_DIR, RUN, SPEC, _start

SHARE_CELLS = ["lfm2_8b_a1b_train", "kanana2_30b_a3b_train",
               "trinity_mini_train", "kimi_linear_48b_a3b_train",
               "qwen3_next_80b_a3b_train"]
WHOLE_CELL = "olmoe_1b7b_train"
OVER_SHARES = ("moe_rows_held_share_window", "moe_rows_traced_over_expected",
               "moe_no_live_rows_share", "moe_rows_held_share_range")
OVER_ALL = "moe_load_max_over_mean_window"
UNITS = {"moe_rows_held_share_window": ("%", "higher"),
         "moe_rows_traced_over_expected": ("ratio", "higher"),
         "moe_no_live_rows_share": ("%", "lower"),
         "moe_rows_held_share_range": ("%", "lower"),
         OVER_ALL: ("ratio", "lower")}

# two expert layers over 4 experts, 8 routed rows a step, three kept steps.
# Layer a holds expert [1, 2): a step with no live row, a collapsed one
# (every row on the held expert), an even one.  Layer b holds [2, 4).
STEPS = [10, 11, 12]
LAYER_A = (1, 1, [[4, 0, 2, 2], [0, 8, 0, 0], [2, 2, 2, 2]])
LAYER_B = (2, 2, [[2, 2, 2, 2], [8, 0, 0, 0], [1, 1, 3, 3]])
# held share by (layer, step), in %: a 0 100 25, b 50 0 75
BY_HAND = {
    "moe_rows_held_share_window": (0 + 100 + 25 + 50 + 0 + 75) / 6.0,
    # the layer-mean by step is 25, 50, 50
    "moe_rows_held_share_range": 50.0 - 25.0,
    # (a, 10) and (b, 11) of six pairs
    "moe_no_live_rows_share": 100.0 * 2 / 6,
    # busiest over mean (2 rows): a 2 4 1, b 1 4 1.5
    OVER_ALL: (2 + 4 + 1 + 1 + 4 + 1.5) / 6.0,
    # the last two steps: live a 8 + 2, b 0 + 6; even routing gives a
    # 8 / 4 and b 8 * 2 / 4 a step
    "moe_rows_traced_over_expected": (8 + 2 + 0 + 6) / (2 * 2.0 + 2 * 4.0),
}


def _read(metric, ctx):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    return RUN.load_module("readers", how["reader"]).read(
        ctx, **how.get("args", {}))


def _ctx(steps=STEPS, trace_steps=2, layers=(LAYER_A, LAYER_B)):
    """A training program of one moe_ffn op a layer, and an executor whose
    step_stats answers with the hand-made history."""
    ops, variables, kept = [], {}, {}
    for i, (offset, held, counts) in enumerate(layers):
        ops.append(types.SimpleNamespace(
            type="moe_ffn", attrs={"expert_offset": offset},
            inputs={"GateUpW": ["gate_up%d" % i]},
            outputs={"TokensPerExpert": ["counts%d" % i]}))
        variables["gate_up%d" % i] = types.SimpleNamespace(shape=(held, 6, 8))
        kept["counts%d" % i] = (np.array(steps, "int64"),
                                np.array(counts, "int32"))
    ops.insert(1, types.SimpleNamespace(type="mul", attrs={}, inputs={},
                                        outputs={}))
    block = types.SimpleNamespace(ops=ops, var=variables.__getitem__)
    main = types.SimpleNamespace(global_block=lambda: block)
    asked, logged = [], []

    def step_stats(program):
        asked.append(program)
        return kept

    return {"exe": types.SimpleNamespace(step_stats=step_stats),
            "main": main, "work": {"trace_steps": trace_steps},
            "log": logged.append, "asked": asked, "logged": logged}


def test_the_five_values_on_a_hand_made_history_are_the_hand_arithmetic():
    ctx = _ctx()
    for metric, want in BY_HAND.items():
        assert _read(metric, ctx) == pytest.approx(want, rel=1e-12), metric
    # five metrics: one read of the history, one line
    assert ctx["asked"] == [ctx["main"]] and len(ctx["logged"]) == 1
    line = ctx["logged"][0]
    assert "3 kept steps (10..12, another program ran after [])" in line
    assert "in %: 25.00 50.00 50.00; layer-mean busiest" in line
    # busiest over mean by (layer, step): a 2 4 1, b 1 4 1.5
    assert "by step: 1.50 4.00 1.25; the last 2 are the traced steps" in line


def test_traced_steps_that_are_not_consecutive_leave_the_traced_metric_out():
    ctx = _ctx(steps=[9, 11, 12], trace_steps=3)
    assert _read("moe_rows_traced_over_expected", ctx) is None
    assert _read(OVER_ALL, ctx) == pytest.approx(BY_HAND[OVER_ALL])
    assert "another program ran after [9]" in ctx["logged"][0]
    assert "NOT consecutive" in ctx["logged"][0]
    # fewer kept steps than a traced slice has
    assert _read("moe_rows_traced_over_expected",
                 _ctx(trace_steps=4)) is None
    # a consecutive tail behind a gap counts
    ctx = _ctx(steps=[7, 11, 12])
    assert _read("moe_rows_traced_over_expected", ctx) == pytest.approx(
        BY_HAND["moe_rows_traced_over_expected"])


def test_an_op_that_holds_every_expert_reads_its_whole_load():
    ctx = _ctx(layers=[(0, 4, LAYER_A[2])])
    assert _read("moe_rows_held_share_window", ctx) == 100.0
    assert _read("moe_rows_traced_over_expected", ctx) == 1.0
    assert _read(OVER_ALL, ctx) == pytest.approx((2 + 4 + 1) / 3.0)


@pytest.mark.parametrize("metric", sorted(UNITS))
def test_without_a_history_the_metric_is_left_out(metric):
    """The parent under this PR's benchmark files: an executor without
    step_stats; a program without the op; a history without the op's
    variable; no step kept."""
    ctx = _ctx()
    logged = []
    assert _read(metric, {"exe": object(), "main": ctx["main"],
                          "work": ctx["work"], "log": logged.append}) is None
    assert _read(metric, {"log": logged.append, "work": ctx["work"]}) is None
    no_op = dict(_ctx(layers=()), asked=[])
    assert _read(metric, no_op) is None
    other = _ctx()
    other["exe"] = types.SimpleNamespace(step_stats=lambda program: {})
    assert _read(metric, other) is None
    assert _read(metric, _ctx(steps=[], layers=[(1, 1, np.zeros((0, 4)))],
                              )) is None
    assert logged == []


def test_registry_entries_are_found_by_name():
    per_layer = {m["name"]: m for m in SPEC["per_layer"]}
    for name, (unit, better) in UNITS.items():
        entry = per_layer[name]
        assert (entry["unit"], entry["better"]) == (unit, better)
        assert entry["source"] == "program_counter"
        assert entry["moves"] == "train_mfu"
        assert entry["layer"] == "Op lowerings + kernels"
        how = RUN.load_json(BENCH_DIR, "layer_metrics", name + ".json")
        assert how["reader"] == "moe_window_stat"
        assert how["args"] == {"metric": name}
        assert "Executor.step_stats" in how["what"]
    for name in OVER_SHARES:
        assert sorted(per_layer[name]["workloads"]) == sorted(SHARE_CELLS)
    assert sorted(per_layer[OVER_ALL]["workloads"]) == sorted(
        SHARE_CELLS + [WHOLE_CELL])
    # the cells that report them are the cells with a moe_ffn, and the
    # last-step metrics they stand beside are still there
    for cell in SPEC["workloads"]:
        reports = {m["name"] for m in RUN.cell_metrics(SPEC["per_layer"],
                                                       cell["name"])}
        assert (OVER_ALL in reports) == ("moe_load_max_over_mean" in reports)
        assert set(OVER_SHARES) <= reports or not set(OVER_SHARES) & reports
        assert (OVER_SHARES[0] in reports) == (
            "moe_rows_held_share" in reports)


@pytest.fixture(scope="module")
def rehearsals():
    """The real command at the data files' tiny sizes on the CPU, traced,
    of a cell that holds a share of its experts and of the one that holds
    them all, side by side."""
    procs = {cell: _start(BENCH_DIR, "--workload", cell, "--seed",
                          "3000000019", "--seconds", "30", "--trace", "1",
                          "--rehearse")
             for cell in ("qwen3_next_80b_a3b_train", WHOLE_CELL)}
    out = {}
    for cell, proc in procs.items():
        stdout, err = proc.communicate(timeout=900)
        assert proc.returncode == 0, err[-2000:]
        out[cell] = stdout
    return out


@pytest.mark.parametrize("cell, metrics", [
    ("qwen3_next_80b_a3b_train", OVER_SHARES + (OVER_ALL,)),
    (WHOLE_CELL, (OVER_ALL,))])
def test_a_traced_rehearsal_prints_the_metrics_its_cell_is_listed_for(
        rehearsals, cell, metrics):
    out = rehearsals[cell]
    line = json.loads(next(
        l for l in out.splitlines()
        if l.startswith("rehearsal line")).split(": ", 1)[1])
    assert line["correct"] and line["failed"] == 0
    got = {m: line["metrics"][m] for m in metrics}
    assert {m: v["unit"] for m, v in got.items()} == {
        m: UNITS[m][0] for m in metrics}
    assert not (set(UNITS) - set(metrics)) & set(line["metrics"])
    assert got[OVER_ALL]["value"] >= 1.0
    logged = [l for l in out.splitlines()
              if l.startswith("moe_window_stat: ")]
    assert len(logged) == 1 and "the last 12 are the traced steps" in logged[0]
    assert "NOT consecutive" not in logged[0]
    if cell != WHOLE_CELL:
        # four of sixteen experts held at the rehearsal's widths
        assert 0.0 < got["moe_rows_held_share_window"]["value"] < 100.0
        assert 0.5 < got["moe_rows_traced_over_expected"]["value"] < 2.0
        assert got["moe_rows_held_share_range"]["value"] >= 0.0
        # the window's mean and the last step's reading, side by side
        assert abs(got["moe_rows_held_share_window"]["value"]
                   - line["metrics"]["moe_rows_held_share"]["value"]) < 10.0
