"""Ops the program's fuse passes rewrote: the sum of the `_*_fused_count`
attributes the passes leave on the Program."""


def read(ctx):
    main = ctx.get("main")
    if main is None:
        return None
    return sum(int(v) for k, v in vars(main).items()
               if k.startswith("_") and k.endswith("_fused_count"))
