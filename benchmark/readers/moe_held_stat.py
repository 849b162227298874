"""Share of a step's routing decisions that fell to the experts this chip
holds, in %: for every `moe_ffn` op of the training program, the sum of
its TokensPerExpert statistic (the persistable [E] int32 the op leaves in
the scope: the router's decisions over all E experts in the last step)
over the held range [expert_offset, expert_offset + E_held) against the sum
over all E; the mean over the layers.  E_held is the leading dimension of
the op's GateUpW.  `train_mfu`'s closed form counts N k E_held / E rows (the
expectation under even routing), whatever the step had: a share under
E_held / E is work that count credits and the chip did not do, not a gain.
The live rows of the last step are logged by layer, with their mean over
that expectation.

None where the program has no moe_ffn op or no step has run; 100 where an
op holds every expert its router chooses among."""


def read(ctx):
    import numpy as np

    main, scope = ctx.get("main"), ctx.get("scope")
    if main is None or scope is None:
        return None
    block = main.global_block()
    shares, live, expected = [], [], []
    for op in block.ops:
        if op.type != "moe_ffn":
            continue
        counts = np.asarray(scope.find_var(op.outputs["TokensPerExpert"][0]))
        held = int(block.var(op.inputs["GateUpW"][0]).shape[0])
        offset = int(op.attrs.get("expert_offset", 0))
        routed = int(counts.sum())
        if not routed:
            return None
        live.append(int(counts[offset:offset + held].sum()))
        expected.append(routed * held / float(counts.size))
        shares.append(100.0 * live[-1] / routed)
    if not shares:
        return None
    ctx["log"]("moe_held_stat: live rows of the last step by layer %s of %d "
               "routed each: %.3f x the %d a layer that even routing gives"
               % (live, routed, sum(live) / sum(expected), expected[0]))
    return float(np.mean(shares))
