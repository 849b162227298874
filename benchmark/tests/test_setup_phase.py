"""The set-up metrics' reader (readers/setup_phase.py) over hand-written
ledger records, the thirteen metrics this PR's data files add (each
registry entry looked up by name), and what the reader answers for a
program that has no ledger: None, and no failure."""

import sys
import types

import pytest

from conftest import BENCH_DIR, RUN, SPEC

SETUP_METRICS = {
    "setup_pre_program_s": ("s", "Device"),
    "import_s": ("s", "Entry"),
    "build_s": ("s", "Program rewrites"),
    "infer_shape_calls": ("count", "Program rewrites"),
    "startup_run_s": ("s", "Entry"),
    "step_analyse_s": ("s", "Trace"),
    "step_trace_s": ("s", "Trace"),
    "step_lower_s": ("s", "Trace"),
    "step_compile_s": ("s", "Trace"),
    "setup_cache_misses": ("count", "Trace"),
    "setup_unspanned_s": ("s", "Entry"),
}
SPAN_METRICS = {"exe_run_ms": "executor.run", "exe_other_ms": "other"}

READER = RUN.load_module("readers", "setup_phase")
MAIN, STARTUP, FORWARD = 1001, 1002, 1003  # program ids
T_START = 100.0


def _counters(infer=(0, 0.0), misses=0):
    return {"infer_shape": {"calls": infer[0], "seconds": infer[1]},
            "compile.cache_misses": {"calls": misses, "seconds": 0.0}}


def _record(name, t0, t1, depth=0, before=None, after=None, **args):
    return {"name": name, "t0": t0, "t1": t1, "args": args, "thread": 1,
            "depth": depth, "counters": before or _counters(),
            "counters_end": after or before or _counters()}


def _ledger():
    """A run's ledger, written by hand.  Process start 100.0; import
    106.0..108.0; the adapter's layers trace 40 shapes in 0.5 s under no
    phase; minimize 109.0..109.6 (backward nested, 10 more traces 0.1 s
    inside it); a pass 109.7..109.9; the startup program 111.0..115.0;
    one small compile outside any phase writes a cache entry; the train
    step 116.0..128.0; later the forward-only program of the reference
    check, which no set-up metric may count."""
    built = _counters((40, 0.5))
    minimized = _counters((50, 0.6))
    stray = _counters((50, 0.6), misses=1)
    return [
        _record("import", 106.0, 108.0),
        _record("build.minimize", 109.0, 109.6, before=built,
                after=minimized),
        _record("build.backward", 109.1, 109.3, depth=1, before=built,
                after=built),
        _record("build.pass", 109.7, 109.9, before=minimized,
                **{"pass": "bf16_amp_pass"}),
        _record("trace_compile", 111.0, 115.0, before=minimized,
                program=STARTUP, path="flat", feed_sig="", analyse_s=0.1,
                trace_s=1.0, lower_s=0.5, backend_compile_s=2.0,
                cache_misses=2),
        _record("trace_compile", 116.0, 128.0, before=minimized, after=stray,
                program=MAIN, path="flat", feed_sig="ids:int32[4, 1024]",
                analyse_s=0.25, trace_s=4.5, lower_s=1.5,
                backend_compile_s=5.5, cache_read_s=5.0, cache_hits=1),
        _record("trace_compile", 160.0, 170.0,
                before=_counters((90, 1.1), misses=1),
                program=FORWARD, path="flat", feed_sig="ids:int32[1, 1024]",
                analyse_s=0.2, trace_s=2.0, lower_s=1.0,
                backend_compile_s=6.0, cache_misses=1),
        # a retrace of the train step (a new feed shape) is not its first
        _record("trace_compile", 180.0, 190.0, program=MAIN, path="flat",
                feed_sig="ids:int32[8, 1024]", analyse_s=9.0, trace_s=9.0),
    ]


def test_split_selects_by_name_program_and_order():
    got = READER.split(_ledger(), T_START, MAIN)
    assert set(got) == set(SETUP_METRICS)
    want = {
        "setup_pre_program_s": 6.0, "import_s": 2.0,
        # minimize 0.6 + pass 0.2 + the 0.5 s of shape traces under no
        # phase (of 0.6 counted before train, 0.1 lie under minimize)
        "build_s": 0.6 + 0.2 + 0.5,
        "infer_shape_calls": 50, "startup_run_s": 4.0,
        "step_analyse_s": 0.25, "step_trace_s": 4.5, "step_lower_s": 1.5,
        "step_compile_s": 5.5,
        # the startup program's two and the stray one; not the forward's
        "setup_cache_misses": 3,
        "setup_unspanned_s": 16.0 - 6.0 - 2.0 - 1.3 - 4.0,
    }
    for metric, value in want.items():
        assert got[metric] == pytest.approx(value), metric
    # no second under two rows: the five rows end where train opens
    assert sum(got[m] for m in (
        "setup_pre_program_s", "import_s", "build_s", "startup_run_s",
        "setup_unspanned_s")) == pytest.approx(116.0 - T_START)


def test_split_answers_what_the_records_can():
    records = _ledger()
    no_train = READER.split(records, T_START, 4242)
    assert set(no_train) == {"setup_pre_program_s", "import_s",
                             "startup_run_s"}
    assert READER.split([], T_START, MAIN) == {}
    # a run whose first compile IS the train step has no startup row, and
    # so no remainder either
    alone = READER.split([records[0], records[5]], T_START, MAIN)
    assert "startup_run_s" not in alone and "setup_unspanned_s" not in alone
    assert alone["step_trace_s"] == 4.5
    # fields JAX never announced (no persistent cache: no event) are left
    # out or read 0, never invented
    bare = _record("trace_compile", 1.0, 2.0, program=MAIN, path="flat")
    got = READER.split([bare], 0.0, MAIN)
    assert "step_trace_s" not in got and got["setup_cache_misses"] == 0


@pytest.mark.parametrize("metric", sorted(SETUP_METRICS))
def test_reader_reads_the_programs_ledger_and_none_without_one(
        metric, monkeypatch):
    how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
    assert how["reader"] == "setup_phase"
    assert how["args"] == {"metric": metric}
    main = object()
    records = _ledger()
    for r in records:
        if r["args"].get("program") == MAIN:
            r["args"]["program"] = id(main)
    profiler = types.ModuleType("paddle_tpu.profiler")
    package = types.ModuleType("paddle_tpu")
    package.profiler = profiler
    monkeypatch.setitem(sys.modules, "paddle_tpu", package)
    monkeypatch.setitem(sys.modules, "paddle_tpu.profiler", profiler)
    ctx = {"t_start": T_START, "main": main}
    # the parent's profiler: no ledger, so nothing to read and no failure
    assert READER.read(ctx, **how["args"]) is None
    profiler.phases = lambda: records
    want = READER.split(records, T_START, id(main))[metric]
    assert READER.read(ctx, **how["args"]) == want
    assert READER.read({"t_start": T_START}, **how["args"]) is None


def test_registry_entries_by_name():
    by_name = {m["name"]: m for m in SPEC["per_layer"]}
    for metric, (unit, layer) in SETUP_METRICS.items():
        assert by_name[metric] == {
            "name": metric, "unit": unit, "better": "lower",
            "source": "program_counter", "layer": layer,
            "moves": "setup_s"}, metric
    for metric, span in SPAN_METRICS.items():
        assert by_name[metric] == {
            "name": metric, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "Entry",
            "moves": "train_mfu"}, metric
        how = RUN.load_json(BENCH_DIR, "layer_metrics", metric + ".json")
        assert (how["reader"], how["args"]) == ("span_ms", {"span": span})
    # every cell reports setup_s, so a metric with no `workloads` list is
    # owed by every cell: none of the thirteen carries one


def test_span_metrics_read_the_profiles_calls():
    span_ms = RUN.load_module("readers", "span_ms")
    calls = [{"executor.run": 5.0, "executor_run": 2.0, "other": 1.5},
             {"executor.run": 7.0, "executor_run": 2.5, "other": 2.5},
             {"executor.run": 6.0, "executor_run": 2.2, "other": 2.0}]

    class Profile:
        @staticmethod
        def profile(ctx):
            return {"calls": calls}

    ctx = {"load_module": lambda subdir, name: Profile}
    assert span_ms.read(ctx, span="executor.run") == 6.0
    assert span_ms.read(ctx, span="other") == 2.0
