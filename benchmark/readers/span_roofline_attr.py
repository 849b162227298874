"""readers/span_roofline.py's roofline share of a named span, over the ops
of a type that carry an attribute: `op` ops with a truthy `attr` alone are
counted for the work, where span_roofline counts every `op` op of the
program.  A program whose layers give one op type two kinds of work under
two name scopes (Trinity-Mini's fused_attention with and without a
`window`, under attn_window > core and attn_full > core) needs the count
to follow the span.  Time, work and peak as span_roofline's; None without
a device trace, without the scopes, or where the program has no such op
(a parent whose builder knows no such attribute reads nothing)."""

import re


def read(ctx, op, span, cost, attr):
    program_profile = ctx["load_module"]("readers", "program_profile")
    prof = program_profile.profile(ctx)
    main = ctx.get("main")
    if prof is None or main is None:
        return None
    n_ops = sum(1 for o in main.global_block().ops
                if o.type == op and o.attrs.get(attr))
    texts = ctx["load_module"]("readers", "hlo_text").texts(ctx)
    if not n_ops or not texts:
        return None
    tr = ctx["load_module"]("", "trace_reduce")
    placed = ctx["load_module"]("readers", "span_roofline").span_members(
        texts, tr.parse_op)
    fluid_op = re.compile(r"[a-z]+/%s(_grad)?/\d+" % re.escape(op))
    inside = re.compile(r"[/(]%s[/)]" % re.escape(span))
    span_ns = sum(ns for name, (ns, _, _, scope, _) in prof["device_ops"]
                  if fluid_op.match(scope or "")
                  and inside.search(placed.get(name, "")))
    if not span_ns or not prof["steps"]:
        return None
    span_s = span_ns * 1e-9 / prof["steps"]
    need = getattr(ctx["adapter"], cost)(ctx["cfg"], ctx["work"])
    peak = ctx["peak"]
    by_flops = n_ops * need["flops_step"] / peak["flops_per_s"]
    by_bytes = n_ops * need["bytes_step"] / peak["hbm_bytes_per_s"]
    ctx["log"]("span_roofline_attr %s[%s]/%s: " % (op, attr, span) + (
        "%d ops, %.3f ms a step on the device; least %.3f ms by operations, "
        "%.3f ms by bytes: bound by %s"
        % (n_ops, 1e3 * span_s, 1e3 * by_flops, 1e3 * by_bytes,
           "operations" if by_flops >= by_bytes else "bytes")))
    return 100.0 * max(by_flops, by_bytes) / span_s
