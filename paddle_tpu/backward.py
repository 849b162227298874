"""Autodiff over the IR (python/paddle/fluid/backward.py analog).

``append_backward(loss)`` (backward.py:469 parity) walks the block's ops in
reverse, emitting one ``<type>_grad`` op per forward op and ``sum`` ops for
fan-in gradient accumulation (_addup_repetitive_outputs_ analog).  Unlike
the reference — where each op type ships a hand-written GradOpDescMaker and
grad kernels — grad ops here carry bookkeeping attrs and are lowered
generically through ``jax.vjp`` of the forward lowering (core/registry.py),
so gradient correctness is inherited from the forward rule.
"""

import numpy as np

from . import framework, unique_name
from .framework import Parameter, Variable, grad_var_name
from .profiler import phase

__all__ = ["append_backward", "calc_gradient"]

_FLOAT_DTYPES = ("float16", "bfloat16", "float32", "float64")


def _is_float_var(block, name):
    v = block._find_var_recursive(name)
    if v is None or v.dtype not in _FLOAT_DTYPES:
        return False
    # tensor arrays are opaque (TensorArray pytrees at trace time) — grads
    # don't flow through them (use the `recurrent` op for trainable loops)
    return getattr(v, "type", None) != framework.VarType.LOD_TENSOR_ARRAY


def _create_grad_var(block, ref_name, grad_name):
    ref = block._find_var_recursive(ref_name)
    return block.create_var(
        name=grad_name,
        shape=ref.shape if ref is not None else None,
        dtype=ref.dtype if ref is not None else "float32",
        persistable=False,
        stop_gradient=True,
    )


def append_backward(loss, parameter_list=None, no_grad_set=None, callbacks=None):
    """Append grad ops for `loss` to its program; return [(param, grad)]."""
    program = loss.block.program
    block = program.global_block()
    no_grad = set(no_grad_set or ())
    with phase("build.backward"), program._op_role_guard("backward"):
        return _append_backward_impl(
            loss, program, block, no_grad, parameter_list
        )


def _append_backward_impl(loss, program, block, no_grad, parameter_list):

    ops = block.ops
    n_fwd = len(ops)  # snapshot: ops appended below must not join the walk
    # backward slice: which ops are on the path to loss
    needed = {loss.name}
    on_path = [False] * n_fwd
    for i in range(n_fwd - 1, -1, -1):
        op = ops[i]
        if op.type.endswith("_grad"):
            continue
        if any(n in needed for n in op.output_arg_names()):
            on_path[i] = True
            needed.update(op.input_arg_names())

    # grad contributions: var -> [grad var names]
    contribs = {}
    finalized = {}
    # grad var name -> the name scope of the op that produced it
    built_under = {}

    def finalize(name):
        """Materialize the single accumulated grad var for `name`."""
        if name in finalized:
            return finalized[name]
        c = contribs.get(name, [])
        if not c:
            return None
        if len(c) == 1:
            finalized[name] = c[0]
            return c[0]
        gname = grad_var_name(name)
        if gname in [x for x in c]:
            gname = unique_name.generate(gname + "_acc")
        _create_grad_var(block, name, gname)
        # the fan-in belongs to its contributions' name scope where they
        # share one, and to none where they differ (a shared weight's)
        scopes = {built_under.get(g, "") for g in c}
        scope = scopes.pop() if len(scopes) == 1 else ""
        block.append_op("sum", inputs={"X": list(c)}, outputs={"Out": [gname]},
                        attrs={"op_namescope": scope} if scope else None)
        built_under[gname] = scope
        finalized[name] = gname
        return gname

    # seed: d loss / d loss = 1
    loss_grad = grad_var_name(loss.name)
    _create_grad_var(block, loss.name, loss_grad)
    block.append_op(
        "fill_constant",
        outputs={"Out": [loss_grad]},
        attrs={
            "shape": list(loss.shape) if loss.shape else [1],
            "dtype": loss.dtype,
            "value": 1.0,
        },
    )
    contribs[loss.name] = [loss_grad]
    finalized[loss.name] = loss_grad

    for i in range(n_fwd - 1, -1, -1):
        if not on_path[i]:
            continue
        op = ops[i]
        # finalize grads of this op's outputs
        out_grads = {}  # slot -> [grad names or None]
        any_grad = False
        for slot, names in op.outputs.items():
            gs = []
            for n in names:
                g = finalize(n)
                gs.append(g)
                if g is not None:
                    any_grad = True
            out_grads[slot] = gs
        if not any_grad:
            continue
        if op.type == "while":
            raise RuntimeError(
                "gradients cannot flow through an unbounded While "
                "(lax.while_loop is not reverse-differentiable); construct "
                "it as layers.While(cond, max_iters=N) to lower to a "
                "differentiable masked scan, or use StaticRNN/DynamicRNN"
            )

        # build grad op inputs: forward inputs + out-grads
        gin = {}
        for slot, names in op.inputs.items():
            gin[slot] = list(names)
        for slot, names in op.outputs.items():
            gs = out_grads[slot]
            if all(g is None for g in gs):
                continue
            filled = []
            for n, g in zip(names, gs):
                if g is None:
                    # zero-fill missing output grads so slot lists align
                    zname = unique_name.generate(grad_var_name(n) + "_zero")
                    _create_grad_var(block, n, zname)
                    block.append_op(
                        "fill_zeros_like",
                        inputs={"X": [n]},
                        outputs={"Out": [zname]},
                    )
                    filled.append(zname)
                else:
                    filled.append(g)
            gin[slot + "@GRAD"] = filled

        # outputs: grads of differentiable float inputs (slots the op's
        # registry entry marks no-grad — e.g. lookup_table Ids, optimizer
        # state — never get grad vars, matching what lower_grad_op produces)
        from .core.registry import OPS

        opdef = OPS.get(op.type)
        no_grad_slots = opdef.no_grad_inputs if opdef else set()
        gout = {}
        fwd_scope = op.attrs.get("op_namescope", "")
        for slot, names in op.inputs.items():
            if slot in no_grad_slots:
                continue
            outs = []
            produce = False
            for n in names:
                v = block._find_var_recursive(n)
                skip = (
                    n in no_grad
                    or not _is_float_var(block, n)
                    or (v is not None and v.stop_gradient and not isinstance(v, Parameter))
                )
                if skip:
                    outs.append(None)
                    continue
                gname = unique_name.generate(grad_var_name(n))
                _create_grad_var(block, n, gname)
                contribs.setdefault(n, []).append(gname)
                built_under[gname] = fwd_scope
                outs.append(gname)
                produce = True
            if produce:
                gout[slot + "@GRAD"] = ["" if o is None else o for o in outs]
        if not gout:
            continue

        # note: grad-output name lists keep positional alignment with the
        # forward input slots ("" = no grad wanted); the tracer skips empties
        block.append_op(
            op.type + "_grad",
            inputs=gin,
            outputs=gout,
            attrs={
                # a grad op belongs to its forward op's name scope
                **({"op_namescope": fwd_scope} if fwd_scope else {}),
                "__fwd_type__": op.type,
                "__fwd_attrs__": dict(op.attrs),
                "__fwd_in_slots__": list(op.inputs.keys()),
                "__fwd_out_slots__": list(op.outputs.keys()),
                "__fwd_out_names__": {k: list(v) for k, v in op.outputs.items()},
                "__fwd_op_idx__": i,
            },
        )

        # in-place updates (a var both read and written by this op — loop
        # carries, assign-into-existing) violate the one-writer assumption
        # the name-keyed accumulator relies on: contributions gathered so
        # far belong to the POST-op version and were just consumed as this
        # op's output grad.  Earlier ops must see only the grad this op
        # produced for its (pre-op) input version.
        in_names = set(op.input_arg_names())
        for n in set(op.output_arg_names()) & in_names:
            if not _is_float_var(block, n):
                continue
            newg = None
            for slot, names in op.inputs.items():
                gnames = gout.get(slot + "@GRAD")
                if not gnames:
                    continue
                for nm, g in zip(names, gnames):
                    if nm == n and g:
                        newg = g
            contribs[n] = [newg] if newg else []
            finalized.pop(n, None)

    # finalize every remaining accumulated grad and publish the name map so
    # calc_gradient (and debuggers) can find grads of arbitrary vars;
    # unconsumed sum ops are dropped by executor DCE
    for name in list(contribs.keys()):
        finalize(name)
    if not hasattr(program, "_grad_names"):
        program._grad_names = {}
    program._grad_names.update(finalized)

    # collect parameter grads
    if parameter_list is not None:
        params = [
            block._find_var_recursive(p) if isinstance(p, str) else p
            for p in parameter_list
        ]
    else:
        params = [
            v
            for v in block.vars.values()
            if isinstance(v, Parameter) and v.trainable
        ]
    params_grads = []
    for p in params:
        g = finalize(p.name)
        if g is None:
            continue
        gv = block._find_var_recursive(g)
        params_grads.append((p, gv))
    return params_grads


def calc_gradient(targets, inputs, target_gradients=None, no_grad_set=None):
    """Gradient of targets w.r.t. arbitrary inputs (backward.py calc_gradient)."""
    if isinstance(targets, Variable):
        targets = [targets]
    if isinstance(inputs, Variable):
        inputs = [inputs]
    assert len(targets) == 1, "calc_gradient: single target supported"
    append_backward(targets[0], parameter_list=None, no_grad_set=no_grad_set)
    block = targets[0].block
    grad_map = getattr(block.program, "_grad_names", {})
    outs = []
    for iv in inputs:
        gname = grad_map.get(iv.name)
        outs.append(block._find_var_recursive(gname) if gname else None)
    return outs
