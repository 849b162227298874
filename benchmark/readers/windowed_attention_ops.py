"""fused_attention ops of the measured Program that carry a `window` > 0:
what a sliding_attention layer builds, read from the ops' attributes.
None where there is no program or it has no fused_attention op at all."""


def read(ctx):
    main = ctx.get("main")
    if main is None:
        return None
    ops = [op for op in main.global_block().ops
           if op.type == "fused_attention"]
    if not ops:
        return None
    return sum(1 for op in ops if int(op.attrs.get("window", 0) or 0) > 0)
