"""Share of the expert-ordered rows that a step's row work ran over, in %:
where a `moe_ffn` op holds a share of its experts, the lowering runs the
gathers, the SwiGLU, the weighting and the un-permute over the chunks of C
rows that hold a live row (the held experts' rows come first in expert
order) and never touches the rest, so a step runs ceil(live / C) * C of
its N k rows, live the rows the router sent to the held experts.  Read as
`moe_rows_held_share` is: live from each op's TokensPerExpert [E] of the
last training step over the held range [expert_offset, expert_offset +
E_held), N k their sum over all E (the op is dropless); C from what the
lowering recorded at trace time,
`kernel_tuning.attribution()["moe_live_chunks"]`: {"ops": lowerings that
took the chunked path, "chunk_rows": {N k: C}}.  The mean over the layers.
It stands at most one chunk a layer above `moe_rows_held_share`; 100 would
say the mechanism never engaged.

None where the program has no moe_ffn op, no step has run, no op holds a
share, or the program records no chunked lowering (a program from before
the mechanism)."""


def read(ctx):
    import numpy as np

    main, scope = ctx.get("main"), ctx.get("scope")
    if main is None or scope is None:
        return None
    from paddle_tpu.ops import kernel_tuning

    said = kernel_tuning.attribution().get("moe_live_chunks")
    if not said or not said.get("ops"):
        return None
    block = main.global_block()
    shares, run = [], []
    for op in block.ops:
        if op.type != "moe_ffn":
            continue
        counts = np.asarray(scope.find_var(op.outputs["TokensPerExpert"][0]))
        held = int(block.var(op.inputs["GateUpW"][0]).shape[0])
        if held == counts.size:
            continue  # every row is live: the whole-size lowering
        offset = int(op.attrs.get("expert_offset", 0))
        routed = int(counts.sum())
        chunk = said["chunk_rows"].get(routed)
        if not routed or not chunk:
            return None
        live = int(counts[offset:offset + held].sum())
        run.append(min(-(-live // chunk) * chunk, routed))
        shares.append(100.0 * run[-1] / routed)
    if not shares:
        return None
    ctx["log"]("moe_run_stat: rows run in the last step by layer %s of %d "
               "each, in chunks of %d; %d lowerings took the chunked path"
               % (run, routed, chunk, said["ops"]))
    return float(np.mean(shares))
