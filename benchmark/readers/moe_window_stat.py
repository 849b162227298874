"""Router statistics over the steps a run kept, not over its last step:
`Executor.step_stats(main)` holds, for every `moe_ffn` op of the training
program, the TokensPerExpert [E] int32 of the last 256 steps (the tail of
warm-up, the window, the traced slice), each under the step number its
`executor.run` span carries.  Per step and layer, as `moe_held_stat` takes
them from the op: live = the counts summed over the held range
[expert_offset, expert_offset + E_held), E_held the leading dimension of
the op's GateUpW; routed = their sum over all E.

  moe_rows_held_share_window     mean over kept steps and layers of
                                 100 live / routed, in %
  moe_rows_traced_over_expected  sum of live over the traced steps and
                                 layers over (sum of routed x E_held / E):
                                 the factor by which the traced run's
                                 `expert_matmul_roofline` and the expert
                                 term of `train_mfu` credit rows the chip
                                 did not run (< 1) or ran uncredited (> 1)
  moe_no_live_rows_share         % of (layer, kept step) pairs in which
                                 this chip held no routed row at all
  moe_rows_held_share_range      max - min over kept steps of the
                                 layer-mean held share, in %: how far a
                                 reading of one step can wander
  moe_load_max_over_mean_window  mean over kept steps and layers of the
                                 busiest expert's rows over the mean

The traced steps are the last `trace_steps` entries: the slice that was
traced last.  That is readers/program_profile.py's own slice where it ran
before this reader (it does wherever the program has the scopes: the time
shares of every cell read it, and so does `expert_matmul_roofline`), else
the loop's.  Their step numbers have to be consecutive, or the traced
metric is left out.  A number the training program did not take went to
another program (the reference check, whose `..._eval_<n>` statistics have
rings of their own, runs between the loop's slice and the profile's).  One
line a cell is logged: the layer-mean held share and the layer-mean busiest
expert over the mean of every kept step, the traced ones last, the numbers
after which another program ran, and the seconds the one `step_stats` call
took.

None where the program keeps no history (`Executor.step_stats` absent: a
program from before it), has no moe_ffn op, or no step has run."""


def summarise(layers, trace_steps):
    """({metric: value}, steps, layer-mean held share by step, layer-mean
    busiest expert over mean by step) from `layers`: one (steps [n] int64,
    counts [n, E], expert_offset, E_held) a moe_ffn op, every op over the
    same steps; None where there is nothing to summarise."""
    import numpy as np

    steps = layers[0][0]
    if not len(steps) or any(not np.array_equal(steps, s)
                             for s, _, _, _ in layers):
        return None
    live = np.stack([c[:, o:o + h].sum(axis=1) for _, c, o, h in layers])
    routed = np.stack([c.sum(axis=1) for _, c, _, _ in layers])
    if not routed.all():
        return None
    # the rows even routing would send this chip: routed x E_held / E
    expected = routed * np.array([[h / float(c.shape[1])]
                                  for _, c, _, h in layers])
    share = 100.0 * live / routed  # [layers, steps]
    by_step = share.mean(axis=0)
    load = np.stack([c.max(axis=1) / c.mean(axis=1) for _, c, _, _ in layers])
    out = {
        "moe_rows_held_share_window": float(share.mean()),
        "moe_no_live_rows_share": 100.0 * float((live == 0).mean()),
        "moe_rows_held_share_range": float(by_step.max() - by_step.min()),
        "moe_load_max_over_mean_window": float(load.mean()),
    }
    n = int(trace_steps)
    traced = steps[-n:]
    if len(traced) == n and np.array_equal(traced, traced[0] + np.arange(n)):
        out["moe_rows_traced_over_expected"] = float(
            live[:, -n:].sum() / expected[:, -n:].sum())
    return out, steps, by_step, load.mean(axis=0)


def _window(ctx):
    import time

    import numpy as np

    exe, main = ctx.get("exe"), ctx.get("main")
    step_stats = getattr(exe, "step_stats", None)
    if step_stats is None or main is None:
        return None
    block = main.global_block()
    ops = [op for op in block.ops if op.type == "moe_ffn"]
    t0 = time.perf_counter()
    kept = step_stats(main) if ops else {}
    read_s = time.perf_counter() - t0
    layers = []
    for op in ops:
        entry = kept.get(op.outputs["TokensPerExpert"][0])
        if entry is None:
            return None
        layers.append((entry[0], np.asarray(entry[1], "int64"),
                       int(op.attrs.get("expert_offset", 0)),
                       int(block.var(op.inputs["GateUpW"][0]).shape[0])))
    n = int(ctx["work"]["trace_steps"])
    said = summarise(layers, n) if layers else None
    if said is None:
        return None
    out, steps, by_step, load = said
    ctx["log"](
        "moe_window_stat: %d kept steps (%d..%d, another program ran after "
        "%s) of %d expert layers; layer-mean held share by step, in %%: %s; "
        "layer-mean busiest expert over mean by step: %s; "
        "the last %d are the traced steps%s; step_stats took %.3f s"
        % (len(steps), steps[0], steps[-1],
           [int(a) for a, b in zip(steps, steps[1:]) if b != a + 1],
           len(layers), " ".join("%.2f" % v for v in by_step),
           " ".join("%.2f" % v for v in load), n,
           "" if "moe_rows_traced_over_expected" in out
           else " (NOT consecutive: traced metric left out)", read_s))
    return out


def read(ctx, metric):
    if "moe_window_stat" not in ctx:  # five metrics, one transfer, one line
        ctx["moe_window_stat"] = _window(ctx)
    return (ctx["moe_window_stat"] or {}).get(metric)
