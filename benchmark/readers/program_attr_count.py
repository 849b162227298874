"""A count by op type that a program rewrite left on the measured Program
as the attribute `attr` (a dict, op type -> ops), summed over `types`.
None where there is no program, the rewrite left no such attribute (a
program from before it did), or none of `types` was counted: a cell whose
Program holds no such op does not report the metric."""


def read(ctx, attr, types):
    by_type = getattr(ctx.get("main"), attr, None)
    if not isinstance(by_type, dict):
        return None
    return sum(int(by_type.get(t, 0)) for t in types) or None
