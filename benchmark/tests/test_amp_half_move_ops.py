"""`amp_half_move_ops`: the data file through its reader on made-up
Programs, the cells that report it against the cells whose built Program
holds a flipped `split`, `concat` or `expand`, and that every such cell's
train step computes the numbers it computed with the three ops held out of
the AMP pass's table."""

import types

import numpy as np
import pytest

from conftest import BENCH_DIR, RUN, SPEC, load_cell

METRIC = "amp_half_move_ops"
# the cells whose built Program holds a flipped split, concat or expand
CHANGED = ("nemotron3_nano_30b_a3b_train", "qwen3_next_80b_a3b_train",
           "kanana2_30b_a3b_train", "kimi_linear_48b_a3b_train",
           "trinity_mini_train", "lfm2_8b_a1b_train")


def _read(ctx):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", METRIC + ".json")
    return RUN.load_module("readers", how["reader"]).read(
        ctx, **how.get("args", {}))


@pytest.mark.parametrize("flipped, value", [
    ({"split": 8, "expand": 2, "reshape2": 24, "causal_conv": 4}, 10),
    ({"split": 2, "expand": 1, "concat": 1}, 4),
    ({"reshape2": 96, "transpose2": 96, "dropout": 48}, None),
    ({}, None),
    (None, None),      # a program from before the pass counted
    ("absent", None),  # no program at all
])
def test_the_reader_sums_the_three_move_ops(flipped, value):
    if flipped == "absent":
        ctx = {}
    elif flipped is None:
        ctx = {"main": types.SimpleNamespace()}
    else:
        ctx = {"main": types.SimpleNamespace(_amp_half_flipped=flipped)}
    assert _read(ctx) == value


def test_the_registry_entry():
    entry = RUN.find(SPEC["per_layer"], METRIC, "metric")
    assert entry == SPEC["per_layer"][-1]
    assert (entry["layer"], entry["moves"], entry["source"], entry["unit"],
            entry["better"]) == ("Program rewrites", "train_mfu",
                                 "program_counter", "count", "higher")
    assert sorted(entry["workloads"]) == sorted(CHANGED)


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_a_cell_reports_it_where_its_program_holds_a_flipped_move_op(cell):
    """Built at the cell's REAL sizes (a Program is built, nothing is
    compiled): the metric reads a count exactly in the cells that list
    it, and the Nemotron cell's is the in-projection's and the
    convolution's split of four mixers and two `expand`s."""
    cfg, work, adapter = load_cell(cell, rehearse=False)
    main = adapter.build(cfg, work)["main"]
    value = _read({"main": main})
    listed = cell in RUN.find(SPEC["per_layer"], METRIC,
                              "metric")["workloads"]
    assert (value is not None) == listed
    if cell == "nemotron3_nano_30b_a3b_train":
        assert value == 10
        assert {t: main._amp_half_flipped.get(t, 0)
                for t in ("split", "concat", "expand")} == {
                    "split": 8, "concat": 0, "expand": 2}


@pytest.mark.parametrize("cell", sorted(CHANGED))
def test_the_step_computes_what_it_computed_with_the_move_ops_held_out(
        cell, monkeypatch):
    """At the rehearsal's widths on the CPU: the loss and every parameter
    gradient of two train steps are EQUAL, array for array, to those of
    the Program built with `split`, `concat` and `expand` held out of the
    pass's table."""
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp

    def steps(hold_out):
        cfg, work, adapter = load_cell(cell)
        with monkeypatch.context() as patch, fluid.unique_name.guard():
            if hold_out:
                patch.setattr(mp, "_TRANSPARENT_OPS", {
                    k: v for k, v in mp._TRANSPARENT_OPS.items()
                    if k not in mp._MOVE_OPS})
            built = adapter.build(cfg, work)
        main = built["main"]
        main.random_seed = built["startup"].random_seed = 1234
        names = sorted(p.name for p in main.global_block().all_parameters()
                       if p.name in main._grad_names)
        batch = adapter.make_batch(cfg, work, 0)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(built["startup"])
            outs = [exe.run(main, feed=batch, fetch_list=[built["loss"]] + [
                main._grad_names[n] for n in names]) for _ in range(2)]
        return _read({"main": main}), ["loss"] + names, outs

    flipped, names, got = steps(False)
    held, _, want = steps(True)
    assert flipped and held is None
    for a, b in zip(got, want):
        for name, x, y in zip(names, a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=name)
