"""Mosaic (Pallas) custom calls that survived into the compiled step:
`tpu_custom_call` occurrences in its optimized HLO.  0 under default flags
until a PR makes the program choose a kernel."""


def read(ctx):
    texts = ctx["load_module"]("readers", "hlo_text").texts(ctx)
    if texts is None:
        return None
    return sum(t.count("tpu_custom_call") for t in texts)
