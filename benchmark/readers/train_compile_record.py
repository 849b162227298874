"""One field of the train step's `trace_compile` record in the program's
set-up ledger (`paddle_tpu.profiler.phases()`): the record chosen as
`setup_phase` chooses it, the first `trace_compile` whose `program` is
id(ctx["main"]), and from its `args` the field a compile left there beside
the times `setup_phase` maps (`state_relayouts`: how many read-write arrays
the compiled step takes in another layout than the one they arrived in).

None where the program keeps no ledger, the run has no such record, or the
record has no such field (a program from before the field was written): the
metric is then left out of the line.  A field that reads 0 is reported as
0."""


def read(ctx, field):
    import paddle_tpu.profiler as profiler

    phases = getattr(profiler, "phases", None)
    if phases is None or ctx.get("main") is None:
        return None
    for record in phases():
        if (record["name"] == "trace_compile"
                and record["args"].get("program") == id(ctx["main"])):
            return record["args"].get(field)
    return None
