"""readers/span_roofline_attr.py's complement: the roofline share of a
named span over the ops of a type that do NOT carry a truthy attribute.
A program whose layers give one op type two kinds of work under two name
scopes (Laguna-XS.2's fused_attention with a `window` under attn_window >
core and without one under attn_full > core) needs the count of the
full-attention cores to follow their span, as span_roofline_attr's follows
the windowed ones; span_roofline would count both kinds for either span.

No second copy of that reader: this one hands span_roofline_attr the
program's `op` ops with the attribute said the other way round, so span,
time, work, peak and the logged line (under span_roofline_attr's name, with
the count of ops WITHOUT the attribute) are that file's.  One reader with
an `attr_absent` argument is a `benchmark` PR's repair (PERF.md section 7).
None without a device trace, without the scopes, or where the program has
no such op."""

import types


def _complement(main, op, attr):
    ops = [types.SimpleNamespace(type=op, attrs={attr: not o.attrs.get(attr)})
           for o in main.global_block().ops if o.type == op]
    return types.SimpleNamespace(
        global_block=lambda: types.SimpleNamespace(ops=ops))


def read(ctx, op, span, cost, attr):
    load = ctx["load_module"]
    main = ctx.get("main")
    if main is None or load("readers", "program_profile").profile(ctx) is None:
        return None
    # the profile and the step's text are kept on ctx: taken from the
    # program itself before a copy of ctx names the complement in its place
    load("readers", "hlo_text").texts(ctx)
    return load("readers", "span_roofline_attr").read(
        dict(ctx, main=_complement(main, op, attr)), op, span, cost, attr)
