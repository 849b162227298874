"""A number the measured loop counted itself (ctx["counters"]): host
clocks around its own calls and deltas of the program's counters
(Executor.compile_count, Executor.host_feed_ms) over the window."""


def read(ctx, key):
    return ctx.get("counters", {}).get(key)
