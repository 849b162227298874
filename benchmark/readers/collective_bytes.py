"""Bytes the compiled step's collectives produce per step: output bytes of
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute
in the optimized HLO (the async `-start` form counted once).  The same
arithmetic as Executor.spmd_comm_stats, kept here so that the yardstick
does not move with the program; benchmark/tests checks the two agree."""

import re

ELEM_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
              "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
              "pred": 1}
PATTERN = re.compile(
    r"=\s+(?:\(?)([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")


def count(texts):
    total = 0
    for txt in texts:
        for m in PATTERN.finditer(txt):
            n = 1
            for d in m.group(2).split(","):
                if d:
                    n *= int(d)
            total += n * ELEM_BYTES.get(m.group(1), 4)
    return total


def read(ctx):
    texts = ctx["load_module"]("readers", "hlo_text").texts(ctx)
    if texts is None:
        return None
    return count(texts)
