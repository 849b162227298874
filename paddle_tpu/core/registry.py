"""Op registry: op type -> JAX lowering rule.

TPU-native analog of the reference's kernel registry
(``paddle/fluid/framework/op_registry.h``).  Where the reference maps
``op_type -> {OpKernelType -> kernel fn}`` and dispatches per-op at runtime,
here each op registers one *lowering rule*: a pure function from traced JAX
arrays (+ static attrs) to traced JAX arrays.  The executor composes these
rules while tracing a Block and XLA compiles/fuses the whole block.

Gradients come from the lowering itself: for any op ``foo``, the op
``foo_grad`` is lowered generically via ``jax.vjp`` of foo's lowering — the
TPU replacement for the reference's per-op ``GradOpDescMaker`` + hand-written
grad kernels (``grad_op_desc_maker.h``).  XLA CSE merges the re-traced
forward with the original where both are the same computation on the same
operands; that is no promise of single compute: under memory pressure the
compiler's rematerialisation ran the vocabulary head's forward twice
(``%fusion.5264.remat``, root PERF.md section 5), and a lowering with a
custom VJP must keep its forward identical on both sides to be merged
(``math_ops.linear_xent_tiled`` leaves its scan one output for it).
"""


import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np

# --- microbatch-rows context -------------------------------------------------
# Pipeline stage tracing sets this around each stage application so
# row-wise randomness (dropout) stays bit-identical to the unpipelined
# program: the op draws its mask over the FULL global batch rows and
# slices out the local microbatch's window.  threefry is counter-based
# per array position, so the full-batch draw is the same no matter which
# device traces it.  Only meaningful for batch-leading tensors; it is
# only ever set during pipeline stage traces.
_MB_ROWS = threading.local()


@contextlib.contextmanager
def microbatch_rows(total_rows, row_offset):
    """Bind (total global batch rows, this microbatch's first row) for the
    enclosed trace.  `row_offset` may be a traced value."""
    prev = getattr(_MB_ROWS, "ctx", None)
    _MB_ROWS.ctx = (total_rows, row_offset)
    try:
        yield
    finally:
        _MB_ROWS.ctx = prev


def current_microbatch_rows():
    """(total_rows, row_offset) when inside microbatch_rows(), else None."""
    return getattr(_MB_ROWS, "ctx", None)


class OpDef:
    def __init__(
        self, type, lower, no_grad_inputs=None, needs_rng=False,
        side_effect=False, handles_selected_rows=False, stat_outputs=None,
    ):
        self.type = type
        self.lower = lower  # fn(ctx, ins: {slot: [arrays]}, attrs) -> {slot: [arrays]}
        self.no_grad_inputs = set(no_grad_inputs or ())
        self.needs_rng = needs_rng
        # side-effecting ops (network sends, barriers) survive DCE even when
        # no fetch depends on their outputs
        self.side_effect = side_effect
        # ops that natively consume SelectedRows sparse grads (the analog of
        # the reference kernels specialized on the SELECTED_ROWS var type);
        # all other ops get inputs densified by the tracer
        self.handles_selected_rows = handles_selected_rows
        # output slots that hold a step statistic: a persistable the op
        # writes anew every step for whoever reads the scope (moe_ffn's
        # TokensPerExpert).  The Executor keeps the last values of those a
        # step does not read back (trace.TracedFunction.stat_names,
        # Executor.step_stats)
        self.stat_outputs = tuple(stat_outputs or ())


OPS = {}


def register(type_, no_grad_inputs=None, needs_rng=False, side_effect=False,
             handles_selected_rows=False, stat_outputs=None):
    """Decorator: register a lowering rule for op `type_`."""

    def deco(fn):
        OPS[type_] = OpDef(type_, fn, no_grad_inputs, needs_rng, side_effect,
                           handles_selected_rows, stat_outputs)
        return fn

    return deco


def get_op(type_):
    if type_ not in OPS:
        raise NotImplementedError(
            "op '%s' has no TPU lowering registered (known: %d ops)"
            % (type_, len(OPS))
        )
    return OPS[type_]


def is_registered(type_):
    return type_ in OPS


class LowerCtx:
    """Per-trace context handed to lowering rules.

    Carries the step RNG key (ops fold in their op index for independent
    streams — the analog of the reference's per-op seed attrs) and trace-wide
    flags.  `platform`: the platform of the device(s) the step is placed
    on ("tpu", "cpu"), as the Executor states it from its place; None
    where a caller did not say (a lowering that chooses by platform then
    asks jax.default_backend()).
    """

    def __init__(self, rng_key=None, is_test=False, scope=None,
                 platform=None):
        self.rng_key = rng_key
        self.is_test = is_test
        self.scope = scope
        self.platform = platform
        self.op_idx = 0
        self.block = None
        self.trace_block = None  # fn(block_idx, env) for control-flow ops

    def rng(self, attrs=None, salt=0):
        """Key for a randomness-consuming op.  The step key (rng_key, which
        the executor advances every run) is always in the mix so seeded
        dropout still varies per step; a nonzero `seed` attr replaces the
        op-position fold so ops sharing a seed share a stream (reference
        per-op seed-attr semantics).  Which generator the key draws from
        is the Executor's choice (Executor._rng_impl: by the platform the
        step is placed on); kernel_tuning.attribution()["rng_draws"]
        counts the requests by it, at trace time (a context without a
        step key, as shape inference's, draws from a placeholder and is
        not counted)."""
        seed = int(attrs.get("seed", 0)) if attrs else 0
        key = self.rng_key
        if key is None:
            key = jax.random.PRNGKey(0)
        else:
            from ..ops.kernel_tuning import note_rng_draw

            typed = jnp.issubdtype(key.dtype, jax.dtypes.prng_key)
            note_rng_draw(
                str(jax.random.key_impl(key)) if typed else "threefry")
        if seed:
            key = jax.random.fold_in(key, seed)
        else:
            key = jax.random.fold_in(key, self.op_idx)
        return jax.random.fold_in(key, salt)


def _is_float(x):
    try:
        return jnp.issubdtype(jnp.result_type(x), jnp.floating)
    except TypeError:
        return False  # opaque values (TensorArray) are not differentiable leaves


def lower_grad_op(ctx, op, ins, attrs):
    """Generic lowering for `<type>_grad` ops via jax.vjp of the forward rule.

    The grad OpDesc (built by backward.py) carries bookkeeping attrs:
      __fwd_type__     : forward op type
      __fwd_attrs__    : forward attrs
      __fwd_in_slots__ : forward input slot names present
      __fwd_out_slots__: forward output slot names
      __fwd_op_idx__   : forward op's index (for RNG parity, e.g. dropout)
    Inputs: forward inputs under their slot names, plus `<out-slot>@GRAD`.
    Outputs: `<in-slot>@GRAD` for differentiable (float) inputs.
    """
    fwd_type = attrs["__fwd_type__"]
    fwd_attrs = attrs["__fwd_attrs__"]
    in_slots = attrs["__fwd_in_slots__"]
    out_slots = attrs["__fwd_out_slots__"]
    opdef = get_op(fwd_type)

    fwd_ins = {s: ins[s] for s in in_slots if s in ins}

    # differentiable leaf positions: float-dtype arrays in forward inputs,
    # minus slots the op marks non-differentiable (e.g. lookup_table Ids)
    diff_pos = []  # (slot, idx)
    for s in in_slots:
        if s in opdef.no_grad_inputs or s not in fwd_ins:
            continue
        for i, v in enumerate(fwd_ins[s]):
            if _is_float(v):
                diff_pos.append((s, i))

    sub_ctx = LowerCtx(ctx.rng_key, ctx.is_test, ctx.scope, ctx.platform)
    sub_ctx.op_idx = attrs.get("__fwd_op_idx__", ctx.op_idx)
    sub_ctx.trace_block = ctx.trace_block
    # mesh-aware lowerings resolve the forward OpDesc (weight names ->
    # partition specs) through ctx.block + op_idx; the grad-side re-run
    # of the forward rule must see the same block or they fall back to
    # replicated operands
    sub_ctx.block = ctx.block

    def fwd_fn(diff_vals):
        merged = {s: list(v) for s, v in fwd_ins.items()}
        for (s, i), v in zip(diff_pos, diff_vals):
            merged[s][i] = v
        outs = opdef.lower(sub_ctx, merged, fwd_attrs)
        flat = []
        for s in out_slots:
            for o in outs.get(s, []):
                flat.append(o)
        return flat

    primals = [fwd_ins[s][i] for (s, i) in diff_pos]
    fwd_flat, vjp_fn = jax.vjp(fwd_fn, primals)

    # cotangents: supplied grads or zeros; non-float outputs (indices, loop
    # conditions) take symbolic-zero float0 cotangents per jax.vjp contract
    cots = []
    k = 0
    for s in out_slots:
        n_out = len(attrs.get("__fwd_out_names__", {}).get(s, [None]))
        gslot = ins.get(s + "@GRAD")
        for i in range(n_out):
            ref = fwd_flat[k]
            k += 1
            if not jnp.issubdtype(jnp.result_type(ref), jnp.inexact):
                cots.append(np.zeros(ref.shape, dtype=jax.dtypes.float0))
            elif gslot is not None and i < len(gslot) and gslot[i] is not None:
                cots.append(jnp.asarray(gslot[i], dtype=ref.dtype).reshape(ref.shape))
            else:
                cots.append(jnp.zeros(ref.shape, ref.dtype))
    (grads,) = vjp_fn(cots)

    outs = {}
    for (s, i), g in zip(diff_pos, grads):
        outs.setdefault(s + "@GRAD", {})[i] = g
    # normalize to lists
    result = {}
    for s, d in outs.items():
        n = max(d.keys()) + 1
        result[s] = [d.get(i) for i in range(n)]
    return result
