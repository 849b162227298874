"""Where `setup_s` goes, from the program's own set-up ledger:
`paddle_tpu.profiler.phases()`, one record for each thing that happens
once a process or once a compile (`import`, `build.*`, `trace_compile`),
each with the process counters (`profiler.counters()`) as it opened and as
it closed.  Records are chosen by name, by program identity and by order,
never by a guess at the clock:

  import         the first record of that name
  startup        the first `trace_compile` of the process
  train          the first `trace_compile` whose `program` is id(ctx["main"])
  before train   the records that opened before it

and the run's set-up is split into rows no second lies under twice (the
loop's clock starts at ctx["t_start"], on time.perf_counter() like the
records):

  setup_pre_program_s   t_start .. import opens: interpreter, `import
                        jax`, backend and chip bring-up
  import_s              the import phase
  build_s               top-level `build.*` phases before train, and the
                        `infer_shape` seconds counted before train under
                        no top-level phase (the layers' own shape traces)
  startup_run_s         the startup record, analysis to end of first call
  setup_unspanned_s     train opens - t_start - the four rows above

so that those five and the loop's `first_step_s` add up to the logged
set-up less the second warm-up step.  Inside the train record:
`step_analyse_s`, `step_trace_s`, `step_lower_s`, `step_compile_s` (its
`analyse_s`, `trace_s`, `lower_s`, `backend_compile_s`: on a cache hit the
last is the read).  Counts: `infer_shape_calls` (before train) and
`setup_cache_misses` (persistent-cache entries written from process start
to the end of train's first call: 0 says the set-up was warm).

A program without the ledger (`profiler.phases` absent) reads as None, and
so does a ledger without the record a metric needs."""


def _length(record):
    return record["t1"] - record["t0"]


def _counter(snapshot, name, field):
    return (snapshot or {}).get(name, {}).get(field, 0)


def split(records, t_start, main_id):
    """{metric: value} of everything the records can answer."""
    out = {}
    imports = [r for r in records if r["name"] == "import"]
    compiles = [i for i, r in enumerate(records)
                if r["name"] == "trace_compile"]
    trains = [i for i in compiles
              if records[i]["args"].get("program") == main_id]
    if imports:
        out["setup_pre_program_s"] = imports[0]["t0"] - t_start
        out["import_s"] = _length(imports[0])
    if compiles and (not trains or compiles[0] != trains[0]):
        out["startup_run_s"] = _length(records[compiles[0]])
    if not trains:
        return out
    train, before = records[trains[0]], records[:trains[0]]
    args = train["args"]
    for metric, field in (("step_analyse_s", "analyse_s"),
                          ("step_trace_s", "trace_s"),
                          ("step_lower_s", "lower_s"),
                          ("step_compile_s", "backend_compile_s")):
        if field in args:
            out[metric] = args[field]
    top = [r for r in before if r["depth"] == 0 and r["t1"] is not None]
    under_a_phase = sum(
        _counter(r["counters_end"], "infer_shape", "seconds")
        - _counter(r["counters"], "infer_shape", "seconds") for r in top)
    out["build_s"] = (
        sum(_length(r) for r in top if r["name"].startswith("build."))
        + _counter(train["counters"], "infer_shape", "seconds")
        - under_a_phase)
    out["infer_shape_calls"] = _counter(train["counters"], "infer_shape",
                                        "calls")
    out["setup_cache_misses"] = (
        sum(records[i]["args"].get("cache_misses", 0)
            for i in compiles if i <= trains[0])
        + _counter(train["counters_end"], "compile.cache_misses", "calls"))
    spanned = ("setup_pre_program_s", "import_s", "build_s", "startup_run_s")
    if all(m in out for m in spanned):
        out["setup_unspanned_s"] = (train["t0"] - t_start
                                    - sum(out[m] for m in spanned))
    return out


def read(ctx, metric):
    import paddle_tpu.profiler as profiler

    phases = getattr(profiler, "phases", None)
    if phases is None or ctx.get("main") is None:
        return None
    return split(phases(), ctx["t_start"], id(ctx["main"])).get(metric)
