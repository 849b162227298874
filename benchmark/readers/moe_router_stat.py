"""Router statistics of a mixture-of-experts program, from what its
`moe_ffn` ops leave in the scope: every op writes the rows each expert was
sent in the last step to a persistable [E] int32 variable (its
TokensPerExpert output).  The training program's are read; the forward
program of the reference check keeps its own.

  load_max_over_mean   busiest expert's rows over the mean, largest layer
  dropped_share        % of the N * top_k routing decisions of a step that
                       reached no expert, over the layers (dropless: 0)

None where the program has no moe_ffn op (another architecture, or a
program from before the op)."""


def read(ctx, stat):
    import numpy as np

    main, scope, work = ctx.get("main"), ctx.get("scope"), ctx["work"]
    if main is None or scope is None:
        return None
    ops = [op for op in main.global_block().ops if op.type == "moe_ffn"]
    if not ops:
        return None
    tokens = int(work["batch"]) * int(work["seq_len"])
    worst, sent, routed = 0.0, 0, 0
    for op in ops:
        counts = np.asarray(scope.find_var(op.outputs["TokensPerExpert"][0]))
        worst = max(worst, float(counts.max()) / float(counts.mean()))
        sent += tokens * int(op.attrs["top_k"])
        routed += int(counts.sum())
    if stat == "load_max_over_mean":
        return worst
    if stat == "dropped_share":
        return 100.0 * (sent - routed) / sent
    raise ValueError("moe_router_stat: unknown stat %r" % stat)
