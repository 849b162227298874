"""A number from the device trace of the traced run, as reduced by
benchmark/trace_reduce.py (ctx["trace"]); nothing where no device was
traced."""


def read(ctx, key, scale=1.0):
    trace = ctx.get("trace")
    if not trace or trace.get(key) is None:
        return None
    return scale * trace[key]
