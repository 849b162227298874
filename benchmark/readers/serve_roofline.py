"""The serve step against the chip's peaks, over the traced slice.

What the steps of the slice REQUIRE (the adapter's closed forms over what
the loop counted: real columns, the keys each may see, rows sampled, live
cache rows read and written; ctx["serve_slice_work"]) against the device
trace (ctx["trace"], benchmark/trace_reduce.py):

  hbm_roofline   % : required bytes / 819 GB/s  over  the device's BUSY
                 seconds.  Bytes bound a decode-heavy step (one column a
                 slot against every weight).  It cannot pass 100: the
                 bytes are the least a step can move (weights once, live
                 rows only, no activation, no logits), and the busy time
                 is everything the device ran in the window, the slot
                 resets included.
  step_mfu       % : required matmul operations / 197 TFLOP/s over the
                 WINDOW's seconds, idle included: the whole step's share of
                 the chip's peak.

The trace's window runs from the second run of the step program to the
last (`steps` runs); the loop counted every step of the slice, so the work
is scaled to the runs the window holds.  None without a device trace, so a
CPU rehearsal reports neither.
"""


def read(ctx, what):
    trace, work = ctx.get("trace"), ctx.get("serve_slice_work")
    adapter, peak = ctx.get("serve_adapter"), ctx.get("peak")
    if (not trace or not work or not work.get("steps")
            or not trace.get("steps") or not trace.get("busy_s")):
        return None
    held = float(trace["steps"]) / work["steps"]
    if what == "hbm_roofline":
        need = adapter.serve_step_bytes(
            ctx["cfg"], ctx["work"], work["steps"], work["rows_read"],
            work["columns"])
        return (100.0 * held * need
                / (peak["hbm_bytes_per_s"] * trace["busy_s"] * ctx["chips"]))
    if what == "step_mfu":
        need = adapter.serve_flops(ctx["cfg"], work["columns"],
                                   work["context_sum"], work["sampled"])
        return (100.0 * held * need
                / (peak["flops_per_s"] * trace["window_s"] * ctx["chips"]))
    raise ValueError("serve_roofline: no quantity %r" % what)
