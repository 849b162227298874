"""Ops of one `type` in the measured Program (forward ops: a grad op has
its own type).  None where there is no program or it has no such op at
all: a program without the mechanism does not report the metric."""


def read(ctx, type):
    main = ctx.get("main")
    if main is None:
        return None
    return sum(1 for op in main.global_block().ops if op.type == type) or None
