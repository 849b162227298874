"""`fc` ops of the measured Program that the lowering runs with bias and
activation on the [M, N] product, the reshape last: those whose
`activation_type` is in the lowering's own rule,
`paddle_tpu.ops.nn_ops.FC_PRODUCT_EPILOGUE_ACTS` (gelu and swish: the
activations whose derivative reads the pre-activation).  0 where the
Program has `fc` ops and none carries one; None where there is no program,
it has no `fc` op at all, or the lowering has no such rule (a program from
before it)."""


def read(ctx):
    from paddle_tpu.ops import nn_ops

    acts = getattr(nn_ops, "FC_PRODUCT_EPILOGUE_ACTS", None)
    main = ctx.get("main")
    if acts is None or main is None:
        return None
    ops = [op for op in main.global_block().ops if op.type == "fc"]
    if not ops:
        return None
    return sum(1 for op in ops
               if (op.attrs.get("activation_type") or "") in acts)
