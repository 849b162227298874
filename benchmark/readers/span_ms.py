"""Host milliseconds one Executor.run call spends under the program's own
span(s) `span` (a name or a list of names, without the "paddle_tpu:"
prefix: summed inside each `executor.run` span), median over the calls of
the traced slice.  The spans are RecordEvents of paddle_tpu/executor.py,
read from the host plane of the device trace.  From
readers/program_profile.py."""

import statistics


def read(ctx, span):
    prof = ctx["load_module"]("readers", "program_profile").profile(ctx)
    if prof is None or not prof["calls"]:
        return None
    names = [span] if isinstance(span, str) else list(span)
    return statistics.median(sum(call.get(n, 0.0) for n in names)
                             for call in prof["calls"])
