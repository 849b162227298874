"""Core IR: Program / Block / Operator / Variable.

TPU-native equivalent of the reference's program representation
(``paddle/fluid/framework/framework.proto`` and
``python/paddle/fluid/framework.py``): a ``Program`` is a list of ``Block``s,
each holding ``Variable``s and a sequence of ``Operator``s (type + named
input/output var lists + attrs).  Unlike the reference — where the program is
interpreted op-by-op by a C++ Executor — here the program is a *compile
artifact*: the executor traces a block's ops through their JAX lowering rules
into one XLA computation per (program, shapes) and runs that on TPU.

Serialization is JSON (stable, dependency-free) rather than protobuf; the
schema mirrors ProgramDesc/BlockDesc/OpDesc/VarDesc fields.
"""

import collections
import contextlib
import copy
import json
import re

import numpy as np

from . import unique_name

__all__ = [
    "Program",
    "Block",
    "Operator",
    "Variable",
    "Parameter",
    "default_main_program",
    "default_startup_program",
    "program_guard",
    "name_scope",
    "grad_var_name",
    "cpu_places",
    "tpu_places",
]

GRAD_VAR_SUFFIX = "@GRAD"
ZERO_VAR_SUFFIX = "@ZERO"


def grad_var_name(var_name):
    return var_name + GRAD_VAR_SUFFIX


class VarType:
    """Mirror of the reference VarType enum (framework.proto:105)."""

    LOD_TENSOR = "lod_tensor"
    SELECTED_ROWS = "selected_rows"
    LOD_TENSOR_ARRAY = "lod_tensor_array"
    STEP_SCOPES = "step_scopes"
    READER = "reader"
    RAW = "raw"


def _to_dtype_str(dtype):
    if dtype is None:
        return None
    if isinstance(dtype, str):
        # normalize
        return np.dtype(dtype).name if dtype not in ("bfloat16",) else "bfloat16"
    try:
        import jax.numpy as jnp

        if dtype == jnp.bfloat16:
            return "bfloat16"
    except Exception:
        pass
    return np.dtype(dtype).name


class Variable:
    """A named tensor slot in a Block (VarDesc analog, framework.py:204)."""

    def __init__(
        self,
        block,
        name=None,
        shape=None,
        dtype=None,
        lod_level=0,
        persistable=False,
        stop_gradient=False,
        type=VarType.LOD_TENSOR,
        is_data=False,
        **kwargs,
    ):
        self.block = block
        if name is None:
            name = unique_name.generate("_generated_var")
        self.name = name
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = _to_dtype_str(dtype) if dtype is not None else "float32"
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.type = type
        self.is_data = is_data
        # op that produced this var (filled by append_op)
        self.op = None

    def __str__(self):
        return "Variable(name=%s, shape=%s, dtype=%s)" % (
            self.name,
            self.shape,
            self.dtype,
        )

    __repr__ = __str__

    # ---- numpy-ish conveniences (math_op_patch analog) -----------------
    def _binary(self, other, op, reverse=False):
        from .layers import math_op_patch

        return math_op_patch.binary(self, other, op, reverse)

    def __add__(self, other):
        return self._binary(other, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "elementwise_sub")

    def __rsub__(self, other):
        return self._binary(other, "elementwise_sub", reverse=True)

    def __mul__(self, other):
        return self._binary(other, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "elementwise_div")

    def __rtruediv__(self, other):
        return self._binary(other, "elementwise_div", reverse=True)

    def __pow__(self, other):
        return self._binary(other, "elementwise_pow")

    def __rpow__(self, other):
        return self._binary(other, "elementwise_pow", reverse=True)

    def __neg__(self):
        from .layers import math_op_patch

        return math_op_patch.scale(self, -1.0)

    def __lt__(self, other):
        return self._binary(other, "less_than")

    def __le__(self, other):
        return self._binary(other, "less_equal")

    def __gt__(self, other):
        return self._binary(other, "greater_than")

    def __ge__(self, other):
        return self._binary(other, "greater_equal")

    def astype(self, dtype):
        from .layers import tensor as tensor_layers

        return tensor_layers.cast(self, dtype)

    def to_dict(self):
        return {
            "name": self.name,
            "shape": list(self.shape) if self.shape is not None else None,
            "dtype": self.dtype,
            "lod_level": self.lod_level,
            "persistable": self.persistable,
            "stop_gradient": self.stop_gradient,
            "type": self.type,
            "is_data": self.is_data,
        }


class Parameter(Variable):
    """A persistable, trainable Variable (framework.py:1977 analog)."""

    def __init__(self, block, shape, dtype, **kwargs):
        kwargs["persistable"] = True
        super().__init__(block, shape=shape, dtype=dtype, **kwargs)
        self.trainable = kwargs.get("trainable", True)
        self.optimize_attr = kwargs.get("optimize_attr", {"learning_rate": 1.0})
        self.regularizer = kwargs.get("regularizer", None)
        self.gradient_clip_attr = kwargs.get("gradient_clip_attr", None)
        self.do_model_average = kwargs.get("do_model_average", None)

    def to_dict(self):
        d = super().to_dict()
        d["is_parameter"] = True
        d["trainable"] = self.trainable
        d["optimize_attr"] = _serializable_optimize_attr(self.optimize_attr)
        return d


class Operator:
    """OpDesc analog: type + named input/output variable-name lists + attrs."""

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        # slot -> [var names]
        self.inputs = {}
        self.outputs = {}
        if inputs:
            for slot, vars_ in inputs.items():
                self.inputs[slot] = [
                    v.name if isinstance(v, Variable) else v for v in _as_list(vars_)
                ]
        if outputs:
            for slot, vars_ in outputs.items():
                self.outputs[slot] = [
                    v.name if isinstance(v, Variable) else v for v in _as_list(vars_)
                ]
        self.attrs = dict(attrs) if attrs else {}
        # OpRole tagging (op_proto_maker.h:26-38 analog): the transpilers
        # (distribute/memory/inference) key off these to classify ops.
        if "op_role" not in self.attrs and block is not None:
            prog = block.program
            self.attrs["op_role"] = getattr(prog, "op_role", "forward")
            rv = getattr(prog, "_op_role_var", None)
            if rv:
                self.attrs["op_role_var"] = list(rv)
        # the name scope the op was built under (op_proto_maker's
        # OpNamescopeAttrName): core/trace.py carries it into the HLO
        if _name_scope_stack and "op_namescope" not in self.attrs:
            self.attrs["op_namescope"] = "/".join(_name_scope_stack)

    def input_arg_names(self):
        return [n for names in self.inputs.values() for n in names if n]

    def output_arg_names(self):
        return [n for names in self.outputs.values() for n in names if n]

    def input(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        return self.outputs.get(slot, [])

    def has_attr(self, name):
        return name in self.attrs

    def attr(self, name):
        return self.attrs[name]

    def _set_attr(self, name, val):
        self.attrs[name] = val

    def __str__(self):
        return "Op(type=%s, inputs=%s, outputs=%s)" % (
            self.type,
            self.inputs,
            self.outputs,
        )

    __repr__ = __str__

    def to_dict(self):
        attrs = {}
        for k, v in self.attrs.items():
            if isinstance(v, np.ndarray):
                attrs[k] = {"__ndarray__": v.tolist(), "dtype": str(v.dtype)}
            elif isinstance(v, (np.integer,)):
                attrs[k] = int(v)
            elif isinstance(v, (np.floating,)):
                attrs[k] = float(v)
            else:
                attrs[k] = v
        return {
            "type": self.type,
            "inputs": self.inputs,
            "outputs": self.outputs,
            "attrs": attrs,
        }


def _as_list(x):
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _serializable_optimize_attr(attr):
    """optimize_attr may hold a Variable (append_LARS writes a per-param
    LR var): serialize it as a {"__var__": name} marker so to_json and
    the binary desc codec stay closed over JSON-able values."""
    if not attr:
        return attr
    return {
        k: {"__var__": v.name} if isinstance(v, Variable) else v
        for k, v in attr.items()
    }


def _resolve_optimize_attr(attr, block):
    """Inverse of _serializable_optimize_attr: markers resolve back to
    the block's Variable once all vars exist (or stay markers when the
    referenced var was pruned away)."""
    if not attr:
        return attr
    out = {}
    for k, v in attr.items():
        if isinstance(v, dict) and set(v) == {"__var__"}:
            resolved = block._find_var_recursive(v["__var__"])
            out[k] = resolved if resolved is not None else v
        else:
            out[k] = v
    return out


class Block:
    """BlockDesc analog: ordered ops + var table, with parent for control flow."""

    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars = collections.OrderedDict()  # name -> Variable
        self.ops = []
        # sub-block attr support for while/cond
        self.forward_block_idx = -1

    @property
    def parent_block(self):
        if self.parent_idx < 0:
            return None
        return self.program.block(self.parent_idx)

    # ---- var management -------------------------------------------------
    def create_var(self, **kwargs):
        name = kwargs.get("name", None)
        if name is not None and name in self.vars:
            return self.vars[name]
        var = Variable(self, **kwargs)
        self.vars[var.name] = var
        return var

    def create_parameter(self, **kwargs):
        param = Parameter(self, kwargs.pop("shape"), kwargs.pop("dtype"), **kwargs)
        # parameters always live in the global (root) block
        gb = self.program.global_block()
        gb.vars[param.name] = param
        param.block = gb
        return param

    def var(self, name):
        v = self._find_var_recursive(name)
        if v is None:
            raise ValueError("Variable %s not found in block %d" % (name, self.idx))
        return v

    def has_var(self, name):
        return self._find_var_recursive(name) is not None

    def has_var_local(self, name):
        return name in self.vars

    def _find_var_recursive(self, name):
        blk = self
        while blk is not None:
            if name in blk.vars:
                return blk.vars[name]
            blk = blk.parent_block
        return None

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # ---- op management --------------------------------------------------
    def _normalize_output_dtypes(self, op):
        """Op construction is where dtype drift enters the IR: a layer
        that creates its output Variable with a raw numpy dtype (or
        mutates ``var.dtype`` after the fact) would serialize
        ``to_dict`` values like ``dtype('float32')`` — desc_codec
        round-trips then stop being byte-stable.  Normalizing at
        append/insert time keeps every op-attached var canonical."""
        for names in op.outputs.values():
            for n in names:
                v = self._find_var_recursive(n) if n else None
                if v is None:
                    continue
                dt = v.dtype
                if dt is not None and not isinstance(dt, str):
                    try:
                        v.dtype = _to_dtype_str(dt)
                    except Exception:
                        pass  # unresolvable: the verifier flags the drift

    def append_op(self, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.append(op)
        if outputs:
            for vars_ in outputs.values():
                for v in _as_list(vars_):
                    if isinstance(v, Variable):
                        v.op = op
        self._normalize_output_dtypes(op)
        self.program._bump_version()
        return op

    def prepend_op(self, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(0, op)
        self._normalize_output_dtypes(op)
        self.program._bump_version()
        return op

    def insert_op(self, index, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(index, op)
        self._normalize_output_dtypes(op)
        self.program._bump_version()
        return op

    def remove_op(self, index):
        del self.ops[index]
        self.program._bump_version()

    def to_dict(self):
        return {
            "idx": self.idx,
            "parent_idx": self.parent_idx,
            "vars": [v.to_dict() for v in self.vars.values()],
            "ops": [op.to_dict() for op in self.ops],
        }


class Program:
    """ProgramDesc analog (framework.py:1404).

    Where the reference interprets this op-by-op (executor.cc:380), the TPU
    executor compiles each (block, input-signature) once via JAX tracing and
    caches the XLA executable.
    """

    def __init__(self):
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self._seed = 0
        self._version = 0
        self._is_test = False
        self.op_role = "forward"
        self._op_role_var = []
        self._appending_grad_times = 0

    @contextlib.contextmanager
    def _op_role_guard(self, role, role_var=None):
        """Tag ops appended inside with an OpRole (and optional
        op_role_var [param, grad] pair) — the op_proto_maker OpRole
        mechanism the reference's transpilers are driven by."""
        prev_role, prev_var = self.op_role, self._op_role_var
        self.op_role = role
        self._op_role_var = list(role_var or [])
        try:
            yield
        finally:
            self.op_role, self._op_role_var = prev_role, prev_var

    def _optimized_guard(self, param_and_grad):
        names = [
            p.name if isinstance(p, Variable) else p
            for p in param_and_grad
            if p is not None
        ]
        return self._op_role_guard("optimize", names)

    # version is used as the executor's compile-cache key component
    def _bump_version(self):
        self._version += 1

    @property
    def random_seed(self):
        return self._seed

    @random_seed.setter
    def random_seed(self, seed):
        self._seed = int(seed)

    def global_block(self):
        return self.blocks[0]

    def block(self, idx):
        return self.blocks[idx]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def create_block(self, parent_idx=None):
        new_idx = len(self.blocks)
        parent = self.current_block_idx if parent_idx is None else parent_idx
        self.blocks.append(Block(self, new_idx, parent))
        self.current_block_idx = new_idx
        self._bump_version()
        return self.current_block()

    def rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    @property
    def num_blocks(self):
        return len(self.blocks)

    def list_vars(self):
        for b in self.blocks:
            for v in b.vars.values():
                yield v

    def all_parameters(self):
        return self.global_block().all_parameters()

    # ---- cloning / pruning ---------------------------------------------
    def clone(self, for_test=False):
        p = copy.deepcopy(self)
        if for_test:
            p._is_test = True
            for b in p.blocks:
                for op in b.ops:
                    if "is_test" in op.attrs:
                        op.attrs["is_test"] = True
                    if op.type == "dropout":
                        op.attrs["is_test"] = True
                    if op.type == "batch_norm":
                        op.attrs["is_test"] = True
        p._bump_version()
        return p

    def _prune(self, targets):
        """Backward-slice the program to the ops needed for `targets`
        (prune.cc analog).  Returns a new Program containing only block 0
        ancestors of the target vars."""
        target_names = set(
            t.name if isinstance(t, Variable) else t for t in _as_list(targets)
        )
        keep = backward_slice_keep(self, target_names)
        p = self.clone()
        pb = p.global_block()
        pb.ops = [op for i, op in enumerate(pb.ops) if keep[i]]
        p._bump_version()
        return p

    # ---- serialization --------------------------------------------------
    def to_json(self):
        return json.dumps(
            {
                "version": 1,
                "random_seed": self._seed,
                "blocks": [b.to_dict() for b in self.blocks],
            }
        )

    @staticmethod
    def from_json(text):
        data = json.loads(text)
        prog = Program()
        prog._seed = data.get("random_seed", 0)
        prog.blocks = []
        for bidx, bd in enumerate(data["blocks"]):
            blk = Block(prog, bd["idx"], bd.get("parent_idx", -1))
            prog.blocks.append(blk)
            for vd in bd["vars"]:
                is_param = vd.pop("is_parameter", False)
                trainable = vd.pop("trainable", True)
                optimize_attr = vd.pop("optimize_attr", None)
                name = vd.pop("name")
                shape = vd.pop("shape")
                if is_param:
                    p = Parameter(blk, shape, vd.pop("dtype"), name=name, **vd)
                    p.trainable = trainable
                    if optimize_attr is not None:
                        p.optimize_attr = optimize_attr
                    blk.vars[name] = p
                else:
                    blk.create_var(name=name, shape=shape, **vd)
            for v in blk.vars.values():
                if isinstance(v, Parameter):
                    v.optimize_attr = _resolve_optimize_attr(
                        v.optimize_attr, blk)
            for od in bd["ops"]:
                attrs = {}
                for k, v in od["attrs"].items():
                    if isinstance(v, dict) and "__ndarray__" in v:
                        attrs[k] = np.array(v["__ndarray__"], dtype=v["dtype"])
                    else:
                        attrs[k] = v
                op = Operator(blk, od["type"], None, None, attrs)
                op.inputs = {k: list(v) for k, v in od["inputs"].items()}
                op.outputs = {k: list(v) for k, v in od["outputs"].items()}
                blk.ops.append(op)
        prog.current_block_idx = 0
        return prog

    def __str__(self):
        lines = []
        for b in self.blocks:
            lines.append("-- block %d (parent %d) --" % (b.idx, b.parent_idx))
            for op in b.ops:
                lines.append("  " + str(op))
        return "\n".join(lines)


def backward_slice_keep(program, target_names):
    """Keep-mask of the global block's ancestor ops of `target_names`
    (prune.cc's reverse walk) — THE shared slicer behind
    ``Program._prune`` and the inference transpiler's fetch-cut.  An op
    owning sub-blocks (while / cond / recompute) counts its sub-blocks'
    external reads as inputs, so a kept control-flow op keeps its
    producers."""
    from .core.trace import op_sub_blocks, sub_block_external_reads

    block = program.global_block()
    needed = set(target_names)
    keep = [False] * len(block.ops)
    for i in range(len(block.ops) - 1, -1, -1):
        op = block.ops[i]
        if any(n in needed for n in op.output_arg_names()):
            keep[i] = True
            needed.update(op.input_arg_names())
            for sub_idx in op_sub_blocks(op):
                bound = op.attrs.get("__bound_names__", ())
                needed.update(sub_block_external_reads(
                    program, program.block(sub_idx), bound))
    return keep


# ---------------------------------------------------------------------------
# default program management
# ---------------------------------------------------------------------------
_main_program_ = Program()
_startup_program_ = Program()


def default_main_program():
    return _main_program_


def default_startup_program():
    return _startup_program_


def switch_main_program(program):
    global _main_program_
    prev = _main_program_
    _main_program_ = program
    return prev


def switch_startup_program(program):
    global _startup_program_
    prev = _startup_program_
    _startup_program_ = program
    return prev


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    prev_main = switch_main_program(main_program)
    prev_startup = None
    if startup_program is not None:
        prev_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(prev_main)
        if prev_startup is not None:
            switch_startup_program(prev_startup)


_name_scope_stack = []


@contextlib.contextmanager
def name_scope(prefix=None):
    """Ops built inside carry `op_namescope`: the prefixes of the
    enclosing name scopes joined by "/".  Grad ops inherit their forward
    op's, and the scope reaches the lowered HLO (core/trace.py), where
    nested prefixes are joined by "." and read back as one word: a prefix
    is made of letters, digits and "_" (another character would drop the
    op out of every reading of its scope, silently).  An empty prefix adds
    nothing."""
    if prefix:
        if not re.fullmatch(r"\w+", prefix):
            raise ValueError(
                "name_scope(%r): a prefix is made of letters, digits and "
                "\"_\"" % (prefix,))
        _name_scope_stack.append(prefix)
    try:
        yield
    finally:
        if prefix:
            _name_scope_stack.pop()


def inherit_namescope(src_op, *new_ops):
    """Ops a rewrite makes for `src_op` (a fused op for its chain, the
    casts around an AMP op) belong to the name scope it was built under."""
    scope = src_op.attrs.get("op_namescope")
    if scope:
        for op in new_ops:
            op.attrs.setdefault("op_namescope", scope)


def cpu_places(device_count=None):
    from .places import CPUPlace

    n = device_count or 1
    return [CPUPlace() for _ in range(n)]


def tpu_places(device_ids=None):
    from .places import TPUPlace
    import jax

    if device_ids is None:
        device_ids = range(len(jax.devices()))
    return [TPUPlace(i) for i in device_ids]
