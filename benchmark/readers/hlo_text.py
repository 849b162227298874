"""Optimized HLO of the measured step, from Executor.compiled_hlo (a
re-lower: traced run only), memoized on ctx so that every reader of it
pays once."""


def texts(ctx):
    if "hlo_texts" not in ctx:
        exe, main = ctx.get("exe"), ctx.get("main")
        ctx["hlo_texts"] = (exe.compiled_hlo(main)
                            if exe is not None and main is not None else None)
    return ctx["hlo_texts"]
