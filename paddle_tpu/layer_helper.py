"""LayerHelper (python/paddle/fluid/layer_helper.py:29 analog).

Layers use this to create parameters (with startup-program init ops,
create_parameter :288), temp output vars, and to append ops.  Compile-time
shape inference — the reference's per-op C++ InferShape on BlockDesc — is
done here generically by abstract-evaluating the op's JAX lowering with
``jax.eval_shape``: one rule per op serves tracing, compilation *and* shape
inference.  Unknown batch dims (-1) ride through as a sentinel extent.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np

from . import framework, unique_name
from .core.registry import LowerCtx, get_op, is_registered
from .initializer import Constant, Xavier
from .param_attr import ParamAttr
from .profiler import counted
from .ops.common import jdt

# sentinel for unknown (-1) dims during abstract shape inference; a large
# prime so collision with a real static extent is practically impossible
# (abstract eval allocates nothing, so the size is free)
_DYN = 1000003


def _abstract_inputs(op, block):
    ins = {}
    for slot, names in op.inputs.items():
        vals = []
        for n in names:
            v = block._find_var_recursive(n)
            if v is None or v.shape is None:
                return None
            shape = tuple(_DYN if d in (-1, None) else int(d) for d in v.shape)
            vals.append(jax.ShapeDtypeStruct(shape, jdt(v.dtype)))
        ins[slot] = vals
    return ins


def infer_shape(op, block):
    """Set output var shapes/dtypes by abstract evaluation of the lowering
    (counted, with its time, under profiler.counters()["infer_shape"])."""
    if not is_registered(op.type):
        return
    ins = _abstract_inputs(op, block)
    if ins is None:
        return
    opdef = get_op(op.type)

    def f(ins_):
        ctx = LowerCtx()  # no step key: rng() hands out a placeholder
        return opdef.lower(ctx, ins_, op.attrs)

    try:
        with counted("infer_shape"):
            outs = jax.eval_shape(f, ins)
    except Exception:
        return
    for slot, names in op.outputs.items():
        shapes = outs.get(slot)
        if shapes is None:
            continue
        for n, s in zip(names, shapes):
            if s is None or not hasattr(s, "shape"):
                continue  # opaque outputs (TensorArray pytrees) carry no shape
            v = block._find_var_recursive(n)
            if v is not None:
                v.shape = tuple(-1 if d == _DYN else d for d in s.shape)
                v.dtype = (
                    "bfloat16" if s.dtype == jnp.bfloat16 else np.dtype(s.dtype).name
                )


class LayerHelper:
    def __init__(self, layer_type, **kwargs):
        self.kwargs = kwargs
        self.layer_type = layer_type
        name = self.kwargs.get("name", None)
        if name is None:
            self.kwargs["name"] = unique_name.generate(layer_type)

    @property
    def name(self):
        return self.kwargs["name"]

    @property
    def main_program(self):
        return framework.default_main_program()

    @property
    def startup_program(self):
        return framework.default_startup_program()

    # ---- inputs ---------------------------------------------------------
    def multiple_input(self, input_param_name="input"):
        inputs = self.kwargs.get(input_param_name, [])
        if isinstance(inputs, framework.Variable):
            return [inputs]
        return list(inputs)

    def input(self, input_param_name="input"):
        inputs = self.multiple_input(input_param_name)
        if len(inputs) != 1:
            raise ValueError("%s layer needs exactly one input" % self.layer_type)
        return inputs[0]

    def input_dtype(self, input_param_name="input"):
        inputs = self.multiple_input(input_param_name)
        dtype = None
        for i in inputs:
            if dtype is None:
                dtype = i.dtype
            elif dtype != i.dtype:
                raise ValueError("mismatched input dtypes")
        return dtype

    # ---- param/bias attr handling ---------------------------------------
    @property
    def param_attr(self):
        return ParamAttr._to_attr(self.kwargs.get("param_attr", None))

    @property
    def bias_attr(self):
        return ParamAttr._to_attr(self.kwargs.get("bias_attr", None))

    def multiple_param_attr(self, length):
        attr = self.param_attr
        if isinstance(attr, ParamAttr):
            attr = [copy.deepcopy(attr) for _ in range(length)]
        return attr

    # ---- creation --------------------------------------------------------
    def create_parameter(
        self, attr, shape, dtype, is_bias=False, default_initializer=None
    ):
        attr = copy.deepcopy(attr) if attr is not None else ParamAttr()
        if default_initializer is None:
            if is_bias:
                attr._set_default_bias_initializer()
            else:
                attr._set_default_param_initializer()
        else:
            attr._set_default_initializer(default_initializer)
        if attr.name is None:
            attr.name = unique_name.generate(".".join([self.name, "w" if not is_bias else "b"]))
        main_block = self.main_program.global_block()
        startup_block = self.startup_program.global_block()
        shape = [int(s) for s in shape]
        shared = main_block.vars.get(attr.name)
        if isinstance(shared, framework.Parameter):
            # a ParamAttr name a layer has used before shares that layer's
            # weight: one parameter, initialised once, however many uses
            if (list(shared.shape) != shape
                    or shared.dtype != framework._to_dtype_str(dtype)):
                raise ValueError(
                    "parameter %r is shared by name as %s %s and asked for "
                    "again as %s %s" % (attr.name, shared.dtype,
                                        list(shared.shape), dtype, shape))
            return shared
        param = main_block.create_parameter(
            shape=shape, dtype=dtype, **{k: v for k, v in attr._to_kwargs().items()}
        )
        # mirror var + init op in the startup program
        sp = startup_block.create_var(
            name=param.name, shape=shape, dtype=dtype, persistable=True
        )
        attr.initializer(sp, startup_block)
        return param

    def create_variable_for_type_inference(self, dtype, stop_gradient=False):
        return self.main_program.current_block().create_var(
            name=unique_name.generate(".".join([self.name, "tmp"])),
            dtype=dtype,
            shape=None,
            persistable=False,
            stop_gradient=stop_gradient,
        )

    # old alias used throughout fluid layer code
    create_tmp_variable = create_variable_for_type_inference

    def create_variable(self, *args, **kwargs):
        return self.main_program.current_block().create_var(*args, **kwargs)

    def create_global_variable(self, persistable=False, *args, **kwargs):
        return self.main_program.global_block().create_var(
            *args, persistable=persistable, **kwargs
        )

    def create_or_get_global_variable(self, name, *args, **kwargs):
        block = self.main_program.global_block()
        if not block.has_var_local(name):
            return self.create_global_variable(name=name, *args, **kwargs)
        return block.vars[name]

    def set_variable_initializer(self, var, initializer):
        sb = self.startup_program.global_block()
        sv = sb.create_var(
            name=var.name, shape=var.shape, dtype=var.dtype, persistable=True
        )
        initializer(sv, sb)

    # ---- op append -------------------------------------------------------
    def append_op(self, type, inputs=None, outputs=None, attrs=None):
        block = self.main_program.current_block()
        op = block.append_op(type, inputs, outputs, attrs)
        infer_shape(op, block)
        return op

    def append_bias_op(self, input_var, dim_start=1, dim_end=None):
        size = input_var.shape[dim_start:dim_end]
        bias_attr = self.bias_attr
        if not bias_attr:
            return input_var
        b = self.create_parameter(
            attr=bias_attr,
            shape=[int(np.prod([d for d in size]))],
            dtype=input_var.dtype,
            is_bias=True,
        )
        tmp = self.create_variable_for_type_inference(dtype=input_var.dtype)
        self.append_op(
            "elementwise_add",
            inputs={"X": [input_var], "Y": [b]},
            outputs={"Out": [tmp]},
            attrs={"axis": dim_start},
        )
        return tmp

    def append_activation(self, input_var):
        act = self.kwargs.get("act", None)
        if act is None:
            return input_var
        if isinstance(act, str):
            act = {"type": act}
        act = copy.deepcopy(act)
        act_type = act.pop("type")
        tmp = self.create_variable_for_type_inference(dtype=input_var.dtype)
        self.append_op(
            act_type, inputs={"X": [input_var]}, outputs={"Out": [tmp]}, attrs=act
        )
        return tmp

    def is_instance(self, param_name, cls):
        param = self.kwargs.get(param_name, None)
        if not isinstance(param, cls):
            raise TypeError("%s must be %s" % (param_name, cls))
