"""Tensor creation / manipulation op lowerings.

Covers the reference's fill/rand init ops, reshape/transpose/concat/split/
slice family, cast, gather/scatter, lookup_table (embedding), one_hot, etc.
(various files under ``paddle/fluid/operators/``).  Random ops draw from the
trace RNG key via ``ctx.rng`` — the functional replacement for the
reference's per-op seed + global generator.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register
from .common import jdt
from .spmd_epilogue import rule_sharded_weight


# ---------------------------------------------------------------------------
# creation ops
# ---------------------------------------------------------------------------
@register("fill_constant")
def _fill_constant(ctx, ins, attrs):
    shape = attrs.get("shape", [1])
    dtype = jdt(attrs.get("dtype", "float32"))
    value = attrs.get("value", 0.0)
    return {"Out": [jnp.full(tuple(int(s) for s in shape), value, dtype=dtype)]}


@register("fill_constant_batch_size_like")
def _fill_constant_bsl(ctx, ins, attrs):
    x = ins["Input"][0]
    shape = list(attrs.get("shape"))
    in_dim = attrs.get("input_dim_idx", 0)
    out_dim = attrs.get("output_dim_idx", 0)
    shape[out_dim] = x.shape[in_dim]
    dtype = jdt(attrs.get("dtype", "float32"))
    return {"Out": [jnp.full(tuple(shape), attrs.get("value", 0.0), dtype=dtype)]}


@register("fill_zeros_like")
def _fill_zeros_like(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [jnp.zeros_like(x)]}


@register("fill_any_like")
def _fill_any_like(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [jnp.full_like(x, attrs.get("value", 0.0))]}


@register("uniform_random", needs_rng=True)
def _uniform_random(ctx, ins, attrs):
    shape = tuple(int(s) for s in attrs["shape"])
    dtype = jdt(attrs.get("dtype", "float32"))
    lo, hi = attrs.get("min", -1.0), attrs.get("max", 1.0)
    out = jax.random.uniform(ctx.rng(attrs), shape, dtype=jnp.float32, minval=lo, maxval=hi)
    return {"Out": [out.astype(dtype)]}


@register("gaussian_random", needs_rng=True)
def _gaussian_random(ctx, ins, attrs):
    shape = tuple(int(s) for s in attrs["shape"])
    dtype = jdt(attrs.get("dtype", "float32"))
    mean, std = attrs.get("mean", 0.0), attrs.get("std", 1.0)
    out = jax.random.normal(ctx.rng(attrs), shape, dtype=jnp.float32) * std + mean
    return {"Out": [out.astype(dtype)]}


@register("truncated_gaussian_random", needs_rng=True)
def _truncated_gaussian_random(ctx, ins, attrs):
    shape = tuple(int(s) for s in attrs["shape"])
    dtype = jdt(attrs.get("dtype", "float32"))
    mean, std = attrs.get("mean", 0.0), attrs.get("std", 1.0)
    out = jax.random.truncated_normal(ctx.rng(attrs), -2.0, 2.0, shape, jnp.float32)
    return {"Out": [(out * std + mean).astype(dtype)]}


@register("randint", needs_rng=True, no_grad_inputs=("X",))
def _randint(ctx, ins, attrs):
    shape = tuple(int(s) for s in attrs["shape"])
    out = jax.random.randint(
        ctx.rng(attrs), shape, attrs.get("low", 0), attrs.get("high", 100)
    )
    return {"Out": [out.astype(jdt(attrs.get("dtype", "int64")))]}


@register("range", no_grad_inputs=("Start", "End", "Step"))
def _range(ctx, ins, attrs):
    # static variant: attrs carry values (layers.arange)
    start = attrs.get("start", 0)
    end = attrs.get("end")
    step = attrs.get("step", 1)
    dtype = jdt(attrs.get("dtype", "int64"))
    return {"Out": [jnp.arange(start, end, step, dtype=dtype)]}


@register("assign")
def _assign(ctx, ins, attrs):
    return {"Out": [ins["X"][0]]}


@register("assign_value")
def _assign_value(ctx, ins, attrs):
    vals = np.array(attrs["values"], dtype=np.dtype(attrs.get("np_dtype", "float32")))
    shape = attrs.get("shape", None)
    if shape:
        vals = vals.reshape(shape)
    return {"Out": [jnp.asarray(vals, dtype=jdt(str(vals.dtype)))]}


@register("shape", no_grad_inputs=("Input",))
def _shape(ctx, ins, attrs):
    x = ins["Input"][0]
    return {"Out": [jnp.array(x.shape, dtype=jnp.int32)]}


# ---------------------------------------------------------------------------
# shape manipulation
# ---------------------------------------------------------------------------
def _resolve_reshape(x, shape):
    shape = list(shape)
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = x.shape[i]
    if -1 in shape:
        known = 1
        for s in shape:
            if s != -1:
                known *= s
        shape[shape.index(-1)] = int(np.prod(x.shape) // known)
    return tuple(shape)


@register("reshape")
@register("reshape2")
def _reshape(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [x.reshape(_resolve_reshape(x, attrs["shape"]))]}


@register("transpose")
@register("transpose2")
def _transpose(ctx, ins, attrs):
    return {"Out": [jnp.transpose(ins["X"][0], attrs["axis"])]}


@register("flatten")
@register("flatten2")
def _flatten(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 1)
    lead = int(np.prod(x.shape[:axis])) if axis > 0 else 1
    return {"Out": [x.reshape(lead, -1)]}


@register("squeeze")
@register("squeeze2")
def _squeeze(ctx, ins, attrs):
    x = ins["X"][0]
    axes = attrs.get("axes", [])
    if not axes:
        return {"Out": [jnp.squeeze(x)]}
    return {"Out": [jnp.squeeze(x, axis=tuple(a % x.ndim for a in axes))]}


@register("unsqueeze")
@register("unsqueeze2")
def _unsqueeze(ctx, ins, attrs):
    x = ins["X"][0]
    for a in sorted(attrs["axes"]):
        x = jnp.expand_dims(x, a)
    return {"Out": [x]}


@register("concat")
def _concat(ctx, ins, attrs):
    return {"Out": [jnp.concatenate(ins["X"], axis=attrs.get("axis", 0))]}


@register("split_byref")
@register("split")
def _split(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    num = attrs.get("num", 0)
    sections = attrs.get("sections", [])
    if sections:
        idx = np.cumsum(sections)[:-1].tolist()
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, num, axis=axis)
    return {"Out": list(outs)}


@register("stack")
def _stack(ctx, ins, attrs):
    return {"Y": [jnp.stack(ins["X"], axis=attrs.get("axis", 0))]}


@register("unstack")
def _unstack(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    outs = [jnp.squeeze(s, axis) for s in jnp.split(x, x.shape[axis], axis)]
    return {"Y": outs}


@register("slice")
def _slice(ctx, ins, attrs):
    x = ins["Input"][0]
    axes, starts, ends = attrs["axes"], attrs["starts"], attrs["ends"]
    idx = [slice(None)] * x.ndim
    for a, s, e in zip(axes, starts, ends):
        dim = x.shape[a]
        s = max(s + dim, 0) if s < 0 else min(s, dim)
        e = max(e + dim, 0) if e < 0 else min(e, dim)
        idx[a] = slice(s, e)
    out = x[tuple(idx)]
    for a in sorted(attrs.get("decrease_axis", []), reverse=True):
        out = jnp.squeeze(out, a)
    return {"Out": [out]}


@register("strided_slice")
def _strided_slice(ctx, ins, attrs):
    x = ins["Input"][0]
    idx = [slice(None)] * x.ndim
    for a, s, e, st in zip(attrs["axes"], attrs["starts"], attrs["ends"], attrs["strides"]):
        idx[a] = slice(s, e, st)
    return {"Out": [x[tuple(idx)]]}


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _tile_copies(x, times, shape):
    """jnp.tile of x of `shape`, whose gradient sums the copies in f32 (or
    wider) and rounds once: at half precision what the f32 op followed by
    a cast gives, so that `expand` only moves values under the AMP trunk
    pass; at f32 the sum jnp.tile's own gradient makes."""
    return jnp.tile(x, times)


def _tile_copies_fwd(x, times, shape):
    return jnp.tile(x, times), None


def _tile_copies_bwd(times, shape, _, g):
    wide = jnp.promote_types(g.dtype, jnp.float32)
    summed = jax.linear_transpose(
        lambda v: jnp.tile(v, times),
        jax.ShapeDtypeStruct(shape, wide))(g.astype(wide))[0]
    return (summed.astype(g.dtype),)


_tile_copies.defvjp(_tile_copies_fwd, _tile_copies_bwd)


@register("expand")
def _expand(ctx, ins, attrs):
    x = ins["X"][0]
    times = tuple(int(t) for t in attrs["expand_times"])
    return {"Out": [_tile_copies(x, times, tuple(x.shape))]}


@register("expand_as")
def _expand_as(ctx, ins, attrs):
    x, y = ins["X"][0], ins["target_tensor"][0]
    reps = [t // s for s, t in zip(x.shape, y.shape)]
    return {"Out": [jnp.tile(x, reps)]}


@register("tile")
def _tile(ctx, ins, attrs):
    return {"Out": [jnp.tile(ins["X"][0], attrs["repeat_times"])]}


@register("cast")
def _cast(ctx, ins, attrs):
    out_dtype = jdt(attrs["out_dtype"])
    return {"Out": [ins["X"][0].astype(out_dtype)]}


@register("pad")
def _pad(ctx, ins, attrs):
    x = ins["X"][0]
    paddings = attrs["paddings"]
    pad_width = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(x.ndim)]
    return {
        "Out": [jnp.pad(x, pad_width, constant_values=attrs.get("pad_value", 0.0))]
    }


@register("pad2d")
def _pad2d(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs["paddings"]  # [top, bottom, left, right]
    mode = attrs.get("mode", "constant")
    pw = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    if attrs.get("data_format", "NCHW") == "NHWC":
        pw = [(0, 0), (p[0], p[1]), (p[2], p[3]), (0, 0)]
    jmode = {"constant": "constant", "reflect": "reflect", "edge": "edge"}[mode]
    kw = {"constant_values": attrs.get("pad_value", 0.0)} if mode == "constant" else {}
    return {"Out": [jnp.pad(x, pw, mode=jmode, **kw)]}


@register("reverse")
def _reverse(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [jnp.flip(x, axis=tuple(attrs["axis"]))]}


@register("roll")
def _roll(ctx, ins, attrs):
    return {"Out": [jnp.roll(ins["X"][0], attrs["shifts"], attrs.get("axis"))]}


# ---------------------------------------------------------------------------
# gather / scatter / embedding
# ---------------------------------------------------------------------------
@register("gather", no_grad_inputs=("Index",))
def _gather(ctx, ins, attrs):
    x, idx = ins["X"][0], ins["Index"][0]
    return {"Out": [jnp.take(x, idx.astype(jnp.int32), axis=attrs.get("axis", 0))]}


@register("gather_nd", no_grad_inputs=("Index",))
def _gather_nd(ctx, ins, attrs):
    x, idx = ins["X"][0], ins["Index"][0].astype(jnp.int32)
    return {"Out": [x[tuple(jnp.moveaxis(idx, -1, 0))]]}


@register("scatter", no_grad_inputs=("Ids",))
def _scatter(ctx, ins, attrs):
    x, ids, updates = ins["X"][0], ins["Ids"][0].astype(jnp.int32), ins["Updates"][0]
    if attrs.get("overwrite", True):
        return {"Out": [x.at[ids].set(updates)]}
    return {"Out": [x.at[ids].add(updates)]}


@register("lookup_table", no_grad_inputs=("Ids",))
@register("lookup_table_v2", no_grad_inputs=("Ids",))
def _lookup_table(ctx, ins, attrs):
    """Rows of W by id.  Under a live training mesh the table is read in
    the shards its partition rule computes it in (vocabulary over mp,
    dividing or not): each rank gathers from its rows, the rows sum over
    mp, and the generic vjp's scatter of lookup_table_grad fills only the
    rank's rows (spmd_epilogue.rule_sharded_weight)."""
    w, ids = ins["W"][0], ins["Ids"][0]
    w = rule_sharded_weight(ctx, ("lookup_table", "lookup_table_v2"), "W", w)
    ids = ids.astype(jnp.int32)
    if ids.ndim >= 2 and ids.shape[-1] == 1:
        ids = ids[..., 0]
    out = jnp.take(w, ids, axis=0)
    pad = attrs.get("padding_idx", -1)
    if pad is not None and pad != -1:
        mask = (ids != pad).astype(out.dtype)[..., None]
        out = out * mask
    return {"Out": [out]}


@register("lookup_table_grad", handles_selected_rows=True)
@register("lookup_table_v2_grad", handles_selected_rows=True)
def _lookup_table_grad(ctx, ins, attrs):
    """Sparse-aware embedding grad (lookup_table_op.cc grad kernel): with
    is_sparse the W gradient is emitted as SelectedRows (ids, rows) —
    never a [vocab, dim] dense tensor — exactly the reference's
    SELECTED_ROWS output var type (selected_rows.h:32).  Dense mode
    falls back to the generic vjp lowering."""
    from ..core.registry import lower_grad_op
    from ..core.selected_rows import SelectedRows

    fwd_attrs = attrs.get("__fwd_attrs__", {})
    if not fwd_attrs.get("is_sparse", False):
        return lower_grad_op(ctx, None, ins, attrs)

    w, ids, og = ins["W"][0], ins["Ids"][0], ins["Out@GRAD"][0]
    ids = ids.astype(jnp.int32)
    if ids.ndim >= 2 and ids.shape[-1] == 1:
        ids = ids[..., 0]
    rows = ids.reshape(-1)
    vals = og.reshape(-1, og.shape[-1]).astype(w.dtype)
    pad = fwd_attrs.get("padding_idx", -1)
    if pad is not None and pad != -1:
        vals = jnp.where((rows == pad)[:, None], 0.0, vals)
    return {"W@GRAD": [SelectedRows(rows, vals, w.shape[0])]}


@register("split_selected_rows", handles_selected_rows=True)
def _split_selected_rows(ctx, ins, attrs):
    """split_selected_rows_op.cc: route a SelectedRows' rows into
    height_sections buckets (the pserver param-shard scatter).  Static
    shapes: every output keeps the full row list, with rows outside its
    section remapped to the out-of-range sentinel (height), which every
    consumer drops; in-section rows are rebased to the section-local
    index, matching the reference's per-shard row numbering."""
    from ..core.selected_rows import SelectedRows

    x = ins["X"][0]
    sections = [int(s) for s in attrs.get("height_sections", [])]
    if not isinstance(x, SelectedRows):
        idx = np.cumsum(sections)[:-1].tolist()
        return {"Out": list(jnp.split(x, idx, axis=0))}
    outs = []
    offset = 0
    for h in sections:
        in_sec = (x.rows >= offset) & (x.rows < offset + h)
        rows = jnp.where(in_sec, x.rows - offset, h)
        vals = jnp.where(in_sec[:, None], x.value, 0)
        outs.append(SelectedRows(rows, vals, h))
        offset += h
    return {"Out": outs}


@register("one_hot", no_grad_inputs=("X",))
def _one_hot(ctx, ins, attrs):
    x = ins["X"][0].astype(jnp.int32)
    if x.ndim >= 2 and x.shape[-1] == 1:
        x = x[..., 0]
    return {"Out": [jax.nn.one_hot(x, attrs["depth"], dtype=jnp.float32)]}


@register("index_select", no_grad_inputs=("Index",))
def _index_select(ctx, ins, attrs):
    x, idx = ins["X"][0], ins["Index"][0].astype(jnp.int32)
    return {"Out": [jnp.take(x, idx, axis=attrs.get("dim", 0))]}


@register("where", no_grad_inputs=("Condition",))
def _where(ctx, ins, attrs):
    return {"Out": [jnp.where(ins["Condition"][0], ins["X"][0], ins["Y"][0])]}


@register("where_index", no_grad_inputs=("Condition",))
def _where_index(ctx, ins, attrs):
    # dynamic-size output: returns padded index list (size = numel)
    cond = ins["Condition"][0]
    idx = jnp.stack(jnp.nonzero(cond, size=cond.size, fill_value=-1), axis=-1)
    return {"Out": [idx.astype(jnp.int32)]}


@register("increment")
def _increment(ctx, ins, attrs):
    x = ins["X"][0]
    # increment_op.cc keeps the input dtype (int step counters stay int)
    return {"Out": [x + jnp.asarray(attrs.get("step", 1.0)).astype(x.dtype)]}


@register("print", no_grad_inputs=("In",), side_effect=True)
def _print(ctx, ins, attrs):
    x = ins["In"][0]
    jax.debug.print(attrs.get("message", "") + " {}", x)
    return {"Out": [x]}


@register("linspace")
def _linspace(ctx, ins, attrs):
    return {
        "Out": [
            jnp.linspace(
                attrs["start"], attrs["stop"], attrs["num"], dtype=jdt(attrs.get("dtype", "float32"))
            )
        ]
    }


@register("eye")
def _eye(ctx, ins, attrs):
    return {
        "Out": [
            jnp.eye(
                attrs["num_rows"],
                attrs.get("num_columns", None),
                dtype=jdt(attrs.get("dtype", "float32")),
            )
        ]
    }


@register("diag")
def _diag(ctx, ins, attrs):
    return {"Out": [jnp.diag(ins["Diagonal"][0])]}


@register("meshgrid")
def _meshgrid(ctx, ins, attrs):
    outs = jnp.meshgrid(*ins["X"], indexing="ij")
    return {"Out": list(outs)}


@register("gaussian_random_batch_size_like", needs_rng=True)
def _gaussian_random_bsl(ctx, ins, attrs):
    x = ins["Input"][0]
    shape = list(attrs["shape"])
    shape[attrs.get("output_dim_idx", 0)] = x.shape[attrs.get("input_dim_idx", 0)]
    out = jax.random.normal(ctx.rng(attrs), tuple(shape)) * attrs.get(
        "std", 1.0
    ) + attrs.get("mean", 0.0)
    return {"Out": [out.astype(jdt(attrs.get("dtype", "float32")))]}


@register("uniform_random_batch_size_like", needs_rng=True)
def _uniform_random_bsl(ctx, ins, attrs):
    x = ins["Input"][0]
    shape = list(attrs["shape"])
    shape[attrs.get("output_dim_idx", 0)] = x.shape[attrs.get("input_dim_idx", 0)]
    out = jax.random.uniform(
        ctx.rng(attrs),
        tuple(shape),
        minval=attrs.get("min", -1.0),
        maxval=attrs.get("max", 1.0),
    )
    return {"Out": [out.astype(jdt(attrs.get("dtype", "float32")))]}


# ---------------------------------------------------------------------------
# static infer rules (analysis/infer.py)
# ---------------------------------------------------------------------------
from ..analysis.infer import (  # noqa: E402
    InferError,
    VarInfo,
    attr_dtype,
    numel_known,
    register_infer,
    same_as,
    slot_info as _i,
)


def _shape_attr_infer(op, ins):
    shape = tuple(int(s) for s in op.attrs.get("shape", [1]))
    return {"Out": [VarInfo(
        shape, attr_dtype(op.attrs.get("dtype"), "float32"))]}


register_infer("fill_constant", req_ins=())(_shape_attr_infer)
register_infer("uniform_random", req_ins=())(_shape_attr_infer)
register_infer("gaussian_random", req_ins=())(_shape_attr_infer)
register_infer("truncated_gaussian_random", req_ins=())(_shape_attr_infer)
register_infer("randint", req_ins=())(_shape_attr_infer)


@register_infer("assign_value", req_ins=())
def _assign_value_infer(op, ins):
    shape = op.attrs.get("shape", None)
    return {"Out": [VarInfo(
        tuple(int(s) for s in shape) if shape else None,
        attr_dtype(op.attrs.get("np_dtype"), "float32"))]}


register_infer("assign", req_ins=("X",))(same_as("X"))
register_infer("fill_zeros_like", req_ins=("X",))(same_as("X"))
register_infer("fill_any_like", req_ins=("X",))(same_as("X"))
register_infer("increment", req_ins=("X",))(same_as("X"))


@register_infer("shape", req_ins=("Input",))
def _shape_op_infer(op, ins):
    x = _i(ins, "Input")
    nd = None if x is None or x.shape is None else len(x.shape)
    return {"Out": [VarInfo((nd,) if nd is not None else None, "int32")]}


@register_infer("reshape", req_ins=("X",))
@register_infer("reshape2", req_ins=("X",))
def _reshape_infer(op, ins):
    x = _i(ins, "X")
    target = [int(s) for s in op.attrs["shape"]]
    xshape = None if x is None else x.shape
    out = []
    for i, s in enumerate(target):
        if s == 0:
            if xshape is None or i >= len(xshape):
                out.append(-1)
            else:
                out.append(xshape[i])
        else:
            out.append(s)
    if -1 in out:
        total = numel_known(xshape) if xshape is not None else None
        known = numel_known([d for d in out if d != -1])
        if total is not None and known:
            if out.count(-1) == 1 and total % known == 0:
                out[out.index(-1)] = total // known
    else:
        total = numel_known(xshape) if xshape is not None else None
        tgt = numel_known(out)
        if total is not None and tgt is not None and total != tgt:
            raise InferError(
                "reshape of %s (%d elements) to %s (%d elements)"
                % (xshape, total, tuple(out), tgt))
    return {"Out": [VarInfo(tuple(out), x.dtype if x else None)]}


@register_infer("transpose", req_ins=("X",))
@register_infer("transpose2", req_ins=("X",))
def _transpose_infer(op, ins):
    x = _i(ins, "X")
    if x is None or x.shape is None:
        return {}
    perm = [int(a) for a in op.attrs["axis"]]
    if sorted(perm) != list(range(len(x.shape))):
        raise InferError(
            "transpose axis %s is not a permutation of rank %d"
            % (perm, len(x.shape)))
    return {"Out": [VarInfo(tuple(x.shape[a] for a in perm), x.dtype)]}


@register_infer("squeeze", req_ins=("X",))
@register_infer("squeeze2", req_ins=("X",))
def _squeeze_infer(op, ins):
    x = _i(ins, "X")
    if x is None or x.shape is None:
        return {}
    axes = op.attrs.get("axes", [])
    if not axes:
        shape = tuple(d for d in x.shape if d != 1)
    else:
        drop = set(int(a) % len(x.shape) for a in axes)
        shape = tuple(d for i, d in enumerate(x.shape) if i not in drop)
    return {"Out": [VarInfo(shape, x.dtype)]}


@register_infer("unsqueeze", req_ins=("X",))
@register_infer("unsqueeze2", req_ins=("X",))
def _unsqueeze_infer(op, ins):
    x = _i(ins, "X")
    if x is None or x.shape is None:
        return {}
    shape = list(x.shape)
    for a in sorted(int(a) for a in op.attrs["axes"]):
        shape.insert(a if a >= 0 else a + len(shape) + 1, 1)
    return {"Out": [VarInfo(tuple(shape), x.dtype)]}


@register_infer("flatten", req_ins=("X",))
@register_infer("flatten2", req_ins=("X",))
def _flatten_infer(op, ins):
    x = _i(ins, "X")
    if x is None or x.shape is None:
        return {}
    axis = int(op.attrs.get("axis", 1))
    lead = numel_known(x.shape[:axis]) if axis > 0 else 1
    tail = numel_known(x.shape[axis:])
    return {"Out": [VarInfo(
        (lead if lead is not None else -1,
         tail if tail is not None else -1), x.dtype)]}


@register_infer("concat", req_ins=("X",))
def _concat_infer(op, ins):
    xs = [v for v in ins.get("X", []) if v is not None]
    if not xs or any(v.shape is None for v in xs):
        return {}
    nd = len(xs[0].shape)
    if any(len(v.shape) != nd for v in xs):
        raise InferError(
            "concat rank mismatch: %s" % [v.shape for v in xs])
    ax = int(op.attrs.get("axis", 0)) % nd
    shape = []
    for i in range(nd):
        if i == ax:
            dims = [v.shape[i] for v in xs]
            shape.append(-1 if any(d < 0 for d in dims) else sum(dims))
        else:
            dims = set(v.shape[i] for v in xs if v.shape[i] >= 0)
            if len(dims) > 1:
                raise InferError(
                    "concat non-axis dim %d mismatch: %s"
                    % (i, [v.shape for v in xs]))
            shape.append(dims.pop() if dims else -1)
    return {"Out": [VarInfo(tuple(shape), xs[0].dtype)]}


@register_infer("stack", req_ins=("X",), req_outs=("Y",))
def _stack_infer(op, ins):
    xs = [v for v in ins.get("X", []) if v is not None]
    if not xs or xs[0].shape is None:
        return {}
    ax = int(op.attrs.get("axis", 0))
    shape = list(xs[0].shape)
    shape.insert(ax if ax >= 0 else ax + len(shape) + 1, len(xs))
    return {"Y": [VarInfo(tuple(shape), xs[0].dtype)]}


@register_infer("slice", req_ins=("Input",))
def _slice_infer(op, ins):
    x = _i(ins, "Input")
    if x is None or x.shape is None:
        return {}
    shape = list(x.shape)
    for a, s, e in zip(op.attrs["axes"], op.attrs["starts"],
                       op.attrs["ends"]):
        a, s, e = int(a), int(s), int(e)
        dim = shape[a]
        if dim < 0:
            continue
        s = max(s + dim, 0) if s < 0 else min(s, dim)
        e = max(e + dim, 0) if e < 0 else min(e, dim)
        shape[a] = max(e - s, 0)
    for a in sorted(
            (int(a) for a in op.attrs.get("decrease_axis", [])),
            reverse=True):
        del shape[a]
    return {"Out": [VarInfo(tuple(shape), x.dtype)]}


@register_infer("cast", req_ins=("X",))
def _cast_infer(op, ins):
    x = _i(ins, "X")
    return {"Out": [VarInfo(
        x.shape if x else None, attr_dtype(op.attrs.get("out_dtype")))]}


@register_infer("gather", req_ins=("X", "Index"))
def _gather_infer(op, ins):
    x, idx = _i(ins, "X"), _i(ins, "Index")
    if x is None or x.shape is None or idx is None or idx.shape is None:
        return {}
    ax = int(op.attrs.get("axis", 0)) % len(x.shape)
    shape = x.shape[:ax] + idx.shape + x.shape[ax + 1:]
    return {"Out": [VarInfo(shape, x.dtype)]}


@register_infer("lookup_table", req_ins=("W", "Ids"))
@register_infer("lookup_table_v2", req_ins=("W", "Ids"))
def _lookup_infer(op, ins):
    w, ids = _i(ins, "W"), _i(ins, "Ids")
    if w is None or w.shape is None or ids is None or ids.shape is None:
        return {}
    ishape = ids.shape
    if len(ishape) >= 2 and ishape[-1] == 1:
        ishape = ishape[:-1]
    return {"Out": [VarInfo(ishape + (w.shape[-1],), w.dtype)]}


@register_infer("one_hot", req_ins=("X",))
def _one_hot_infer(op, ins):
    x = _i(ins, "X")
    if x is None or x.shape is None:
        return {}
    shape = x.shape
    if len(shape) >= 2 and shape[-1] == 1:
        shape = shape[:-1]
    return {"Out": [VarInfo(shape + (int(op.attrs["depth"]),), "float32")]}


register_infer("expand", req_ins=("X",))(None)
register_infer("split", req_ins=("X",))(None)
register_infer("scatter", req_ins=("X", "Ids", "Updates"))(same_as("X"))
