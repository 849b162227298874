"""Roofline share of one named span inside a Fluid op's lowering, in %:
the least time the chip could take for the span's work over the device
time its ops took.

  time   readers/program_profile.py's traced slice: the busy time each
         device op owns, joined to the optimized HLO, whose op_name says
         under which named scope an instruction was traced
         (".../forward/moe_ffn/30/experts/jit(gmm)/pallas_call",
         ".../backward/moe_ffn_grad/57/transpose(...)/jvp(experts)/...").
         A device op is in the span when its Fluid scope is an `op` (or
         its _grad) and the matmul / custom call inside its fusion, else
         its root, else the instruction itself, was traced under
         "/<span>/" or "(<span>)".
  work   the adapter's `cost`(cfg, work) -> {"flops_step", "bytes_step"},
         a closed form over the shapes, per op and step; the program's
         `op` ops are counted from the Program.
  peak   benchmark/peaks.json: the bound is the larger of operations over
         peak FLOP/s and bytes over peak HBM bytes/s; which one is logged.

None without a device trace, without the scopes, or where the program has
no such op."""

import re

CALL = re.compile(r"\bcalls=(%[\w.\-]+)")
OP_NAME = re.compile(r'op_name="([^"]*)"')
HEAVY = ("convolution", "dot", "custom-call")


def span_members(texts, parse_op):
    """{instruction name: op_name that places it} of optimized HLO texts:
    for a fusion the op_name of the matmul or custom call inside its
    fused computation, else of its root; else the instruction's own."""
    own, calls, comps, current = {}, {}, {}, None
    for text in texts:
        for line in text.splitlines():
            if not line.startswith(" "):
                head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(", line)
                current = (comps.setdefault(head.group(1), [])
                           if head and line.rstrip().endswith("{") else None)
                continue
            body = line.strip()
            root = body.startswith("ROOT ")
            body = body[5:] if root else body
            if " = " not in body or current is None:
                continue
            name, opcode, _ = parse_op(body)
            found = OP_NAME.search(body)
            own[name] = found.group(1) if found else ""
            called = CALL.search(body)
            if called:
                calls[name] = called.group(1)
            current.append((name, opcode, root))
    placed = {}
    for name, op_name in own.items():
        members = comps.get(calls.get(name), [])
        heavy = [own[m] for m, opcode, _ in members
                 if opcode in HEAVY and own[m]]
        roots = [own[m] for m, _, root in members if root and own[m]]
        placed[name] = (heavy or roots or [op_name])[0] or op_name
    return placed


def read(ctx, op, span, cost):
    program_profile = ctx["load_module"]("readers", "program_profile")
    prof = program_profile.profile(ctx)
    main = ctx.get("main")
    if prof is None or main is None:
        return None
    n_ops = sum(1 for o in main.global_block().ops if o.type == op)
    texts = ctx["load_module"]("readers", "hlo_text").texts(ctx)
    if not n_ops or not texts:
        return None
    tr = ctx["load_module"]("", "trace_reduce")
    placed = span_members(texts, tr.parse_op)
    fluid_op = re.compile(r"[a-z]+/%s(_grad)?/\d+" % re.escape(op))
    # "/experts/" forward, "jvp(experts)" in what autodiff derives from it
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
    ctx["log"]("span_roofline %s/%s: " % (op, span) + (
        "%.3f ms a step on the device; least %.3f ms by operations, %.3f ms "
        "by bytes: bound by %s"
        % (1e3 * span_s, 1e3 * by_flops, 1e3 * by_bytes,
           "operations" if by_flops >= by_bytes else "bytes")))
    return 100.0 * max(by_flops, by_bytes) / span_s
