"""Share of device 0's busy time that the ops of some Fluid scopes own, in
%: `match` is a regular expression matched at the start of the scope path
"<op_role>/<op type>/<index>[/<nested>...]" that core/trace.py gives every
lowered op ("forward/", "(optimize|lrsched)/", "backward/matmul_grad/");
"$" fits the ops with no scope found.  From readers/program_profile.py."""


def read(ctx, match):
    program_profile = ctx["load_module"]("readers", "program_profile")
    prof = program_profile.profile(ctx)
    if prof is None or not prof["busy_ms"]:
        return None
    return 100.0 * program_profile.scope_ms(prof, match) / prof["busy_ms"]
