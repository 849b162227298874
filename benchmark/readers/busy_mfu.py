"""MFU with idle time taken out: the operations one step requires (the
adapter's closed form) over the time the device was busy per step (device
trace) and the chips' peak."""


def read(ctx):
    trace, peak = ctx.get("trace"), ctx.get("peak")
    if not trace or not trace.get("steps") or not trace.get("busy_s"):
        return None
    busy_per_step = trace["busy_s"] / trace["steps"]
    return (100.0 * ctx["flops_per_step"]
            / (busy_per_step * ctx["chips"] * peak["flops_per_s"]))
