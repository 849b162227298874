"""Occurrences of `pattern` (plain text) in the compiled step's optimized
HLO: an instruction the compiler kept, counted like mosaic_calls.py counts
`tpu_custom_call`.  The pattern is the metric's data file's."""


def read(ctx, pattern):
    texts = ctx["load_module"]("readers", "hlo_text").texts(ctx)
    if texts is None:
        return None
    return sum(t.count(pattern) for t in texts)
