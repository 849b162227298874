"""fused_attention ops of the measured Program whose V is of another width
than its Q: what latent attention builds (scores over nope + rope, values
over v_head_dim), read from the Program's variable shapes.  None where
there is no program or it has no fused_attention op at all."""


def read(ctx):
    main = ctx.get("main")
    if main is None:
        return None
    block = main.global_block()
    ops = [op for op in block.ops if op.type == "fused_attention"]
    if not ops:
        return None
    return sum(1 for op in ops
               if block.var(op.inputs["V"][0]).shape[-1]
               != block.var(op.inputs["Q"][0]).shape[-1])
