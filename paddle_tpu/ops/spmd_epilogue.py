"""shard_map dispatch for the matmul-epilogue pallas kernels under a
GSPMD mesh — closing the PR 14 documented limit that the epilogue
kernels operand-replicate inside a sharded step.

``pallas_call`` has no SPMD partition rule: inside a GSPMD-stamped
program an unwrapped kernel forces XLA to all-gather every operand onto
each device, run the full kernel everywhere, and throw n-1 copies of
the work away.  The qvec-attention lowering already solved this for the
ragged serving step (``_qvec_attention_mesh``); this module generalizes
the recipe to the fc / fused_swiglu / fused_residual_ln lowerings:

1. resolve the op's WEIGHT NAMES from the OpDesc being lowered
   (``ctx.block.ops[ctx.op_idx]`` — the grad-side re-run of a forward
   rule sees the same block through ``lower_grad_op``),
2. look the names up in the live rule table (``current_spmd``) to
   classify the layout — column-parallel, row-parallel, or
   replicated-weights-with-dp-sharded-rows,
3. run the SAME custom_vjp kernel per shard inside ``shard_map`` with
   matching in/out specs.  ``check_vma=False`` autodiff supplies the
   transpose-side psums for replicated operands; the only hand-written
   collective is the mathematical one (the row-parallel epilogue's
   partial-sum psum).

Block sizes inside shard_map are the deterministic defaults computed
from the LOCAL shard shapes — a per-shard tuning search would attribute
collective time to block sizes (the qvec precedent).

Every wrapper returns None when it declines (no mesh, mp=1 and dp=1,
weight name unresolvable, layout not divisible).  With no live mesh the
caller then runs the unwrapped kernel — at mp=1 that keeps the
single-device trace BIT-IDENTICAL; under a live mesh it lowers densely
(pallas_kernels.use_pallas_unwrapped: XLA cannot partition a Mosaic
custom call).
"""

import jax
import jax.numpy as jnp

__all__ = [
    "mesh_ctx", "op_weight_name", "rule_sharded_weight",
    "grad_in_param_storage", "spmd_matmul_bias_act",
    "spmd_matmul_swiglu", "spmd_add_layer_norm", "spmd_flash_attention",
]


def mesh_ctx():
    """(mesh, rules, mp_axis, nsh, dp_axis, ndp) when tracing under a
    live spmd_lowering context with something to shard over, else
    None."""
    from ..parallel.mesh import mesh_axis_sizes
    from ..parallel.partition_rules import current_spmd

    spmd = current_spmd()
    if spmd is None:
        return None
    mesh, rules = spmd
    sizes = mesh_axis_sizes(mesh)
    mp = rules.mp_axis
    nsh = int(sizes.get(mp, 1))
    dp_axis = getattr(rules, "dp_axis", None)
    ndp = int(sizes.get(dp_axis, 1)) if dp_axis else 1
    if nsh <= 1 and ndp <= 1:
        return None
    return mesh, rules, mp, nsh, dp_axis, ndp


def _lowered_op(ctx, op_types):
    """The OpDesc being lowered, resolved through ctx.block + ctx.op_idx
    ((block_idx << 20) | idx on the forward trace, the plain forward
    index on the grad-side re-run).  None when the context carries no
    block or the op there is none of `op_types` (a lowering called from
    another op's)."""
    blk = getattr(ctx, "block", None)
    if blk is None:
        return None
    idx = int(getattr(ctx, "op_idx", 0)) & ((1 << 20) - 1)
    if idx >= len(blk.ops) or blk.ops[idx].type not in op_types:
        return None
    return blk.ops[idx]


def op_weight_name(ctx, expected_type, slot):
    """The var name feeding `slot` of the op being lowered (_lowered_op).
    None when the context carries no block or the op type disagrees —
    callers MUST fall back to the unwrapped kernel then."""
    op = _lowered_op(ctx, (expected_type,))
    names = op.input(slot) if op is not None else ()
    return names[0] if names else None


def _uneven(name, shape):
    """(computed, stored) NamedShardings of a weight that a live
    TRAINING mesh (the rule table names a dp axis) stores in another
    sharding than its rule computes it in: the rule names an axis that
    does not divide the dim, so the divisibility guard stores it
    replicated (a jax.Array argument needs even shards) while
    PartitionRules.compute_spec_for still shards the value, unevenly.
    None everywhere else: no mesh, a serving table (no dp axis), a name
    with no rule, every dim dividing."""
    mc = mesh_ctx()
    if mc is None or not mc[4] or name is None:
        return None
    from jax.sharding import NamedSharding

    mesh, rules = mc[0], mc[1]
    stored = rules.sharding_for(mesh, name, shape)
    computed = NamedSharding(mesh, rules.compute_spec_for(mesh, name, shape))
    if computed.is_equivalent_to(stored, len(shape)):
        return None
    return computed, stored


def rule_sharded_weight(ctx, op_types, slot, w):
    """`w`, the weight feeding `slot` of the op being lowered (one of
    `op_types`), constrained to the uneven shards its rule computes it in
    where storage and computation part (_uneven); without the constraint
    every rank of the axis computes the whole of a replicated weight.
    The constraint's transpose puts the weight's gradient in the same
    shards.  Anywhere else `w` comes back as it is — a dividing dim is
    stored as it is computed, and a serving step's local gather from a
    replicated table beats a sharded gather and an all-reduce (pooled ==
    solo stays bit for bit)."""
    from .kernel_tuning import note_uneven_constraint

    op = _lowered_op(ctx, op_types)
    names = op.input(slot) if op is not None else ()
    found = _uneven(names[0] if names else None, tuple(w.shape))
    if found is None:
        return w
    note_uneven_constraint(op.type)
    return jax.lax.with_sharding_constraint(w, found[0])


def grad_in_param_storage(op, ins):
    """The boundary back to storage: the dense `Grad` of an optimizer op
    whose `Param` is computed in uneven shards (_uneven) constrained to
    the Param's STORED sharding — the one all-gather of the summed
    gradient.  Left alone, the partitioner runs the update in shards and
    gathers each of ParamOut and the accumulators instead."""
    from ..core.selected_rows import SelectedRows

    if not (op.input("Param") and op.input("Grad")):
        return ins
    g = ins["Grad"][0]
    if isinstance(g, SelectedRows):
        return ins
    found = _uneven(op.input("Param")[0], tuple(g.shape))
    if found is None:
        return ins
    return dict(ins, Grad=[jax.lax.with_sharding_constraint(g, found[1])])


def _dim_has(spec, d, axis):
    """Does PartitionSpec `spec` place mesh axis `axis` on dim `d`?"""
    if spec is None or len(spec) <= d:
        return False
    e = tuple(spec)[d]
    return e == axis or (isinstance(e, tuple) and axis in e)


def _row_axis(dp_axis, ndp, rows):
    """The activation-rows mesh axis: the dp axis when it exists and
    divides the flattened row count, else None (rows replicate)."""
    return dp_axis if (dp_axis and ndp > 1 and rows % ndp == 0) else None


def _shard_map(mesh, body, in_specs, out_specs):
    from ..parallel.mesh import shard_map

    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def spmd_matmul_bias_act(ctx, x2, w, bias, act):
    """Mesh-aware matmul_bias_act: column-parallel (w P(·, mp): local
    columns, no collective — bias slices with its column), row-parallel
    (w P(mp, ·): partial sums psum'd, bias + act applied AFTER the
    combine), or replicated-w with dp-sharded rows.  None -> unwrapped."""
    from jax.sharding import PartitionSpec as P

    from .pallas_kernels import _mm_act, _mm_col_block, _row_block, \
        matmul_bias_act

    mc = mesh_ctx()
    if mc is None:
        return None
    mesh, rules, mp, nsh, dp_axis, ndp = mc
    wname = op_weight_name(ctx, "fc", "W")
    if wname is None:
        return None
    spec = rules.spec_for(wname, tuple(w.shape))
    M, K = x2.shape
    N = w.shape[1]
    row = _row_axis(dp_axis, ndp, M)
    nrow = ndp if row else 1
    col_par = nsh > 1 and _dim_has(spec, 1, mp) and N % nsh == 0
    row_par = nsh > 1 and _dim_has(spec, 0, mp) and K % nsh == 0

    if col_par:
        bm = _row_block(M // nrow, 256)
        bn = _mm_col_block(N // nsh, 256)

        def body(xl, wl, bl):
            return matmul_bias_act(xl, wl, bl, act, bm, bn)

        in_specs = (P(row, None), P(None, mp), P(mp))
        out_spec = P(row, mp)
        if bias is None:
            body, in_specs = (lambda xl, wl:
                              matmul_bias_act(xl, wl, None, act, bm, bn)
                              ), in_specs[:2]
            return _shard_map(mesh, body, in_specs, out_spec)(x2, w)
        return _shard_map(mesh, body, in_specs, out_spec)(x2, w, bias)

    if row_par:
        bm = _row_block(M // nrow, 256)
        bn = _mm_col_block(N, 256)

        def body(xl, wl, *b):
            z = matmul_bias_act(xl, wl, None, "", bm, bn)
            z = jax.lax.psum(z.astype(jnp.float32), mp)
            if b:
                z = z + b[0].reshape(1, -1).astype(jnp.float32)
            return _mm_act(z, act).astype(xl.dtype)

        in_specs = (P(row, mp), P(mp, None))
        args = (x2, w)
        if bias is not None:
            in_specs = in_specs + (P(None),)
            args = args + (bias,)
        return _shard_map(mesh, body, in_specs, P(row, None))(*args)

    if row is None:
        return None
    bm = _row_block(M // nrow, 256)
    bn = _mm_col_block(N, 256)

    def body(xl, wl, *b):
        return matmul_bias_act(xl, wl, b[0] if b else None, act, bm, bn)

    in_specs = (P(row, None), P(None, None))
    args = (x2, w)
    if bias is not None:
        in_specs = in_specs + (P(None),)
        args = args + (bias,)
    return _shard_map(mesh, body, in_specs, P(row, None))(*args)


def spmd_matmul_swiglu(ctx, x2, wg, wu):
    """Mesh-aware matmul_swiglu: the gate/up pair is column-parallel
    when BOTH weights carry P(·, mp) (silu and the product are
    element-wise in the sharded column space); otherwise rows-only when
    dp divides."""
    from jax.sharding import PartitionSpec as P

    from .pallas_kernels import _mm_col_block, _row_block, matmul_swiglu

    mc = mesh_ctx()
    if mc is None:
        return None
    mesh, rules, mp, nsh, dp_axis, ndp = mc
    gname = op_weight_name(ctx, "fused_swiglu", "GateW")
    uname = op_weight_name(ctx, "fused_swiglu", "UpW")
    if gname is None or uname is None:
        return None
    gspec = rules.spec_for(gname, tuple(wg.shape))
    uspec = rules.spec_for(uname, tuple(wu.shape))
    M, K = x2.shape
    N = wg.shape[1]
    row = _row_axis(dp_axis, ndp, M)
    nrow = ndp if row else 1
    col_par = (nsh > 1 and N % nsh == 0
               and _dim_has(gspec, 1, mp) and _dim_has(uspec, 1, mp))
    if not col_par and (row is None or _dim_has(gspec, 1, mp)
                        or _dim_has(uspec, 1, mp)):
        return None
    wspec = P(None, mp) if col_par else P(None, None)
    ncol = nsh if col_par else 1
    bm = _row_block(M // nrow, 256)
    bn = _mm_col_block(N // ncol, 256)

    def body(xl, wgl, wul):
        return matmul_swiglu(xl, wgl, wul, bm, bn)

    return _shard_map(
        mesh, body, (P(row, None), wspec, wspec),
        P(row, mp) if col_par else P(row, None))(x2, wg, wu)


def spmd_add_layer_norm(ctx, x2, y2, gamma, beta, eps):
    """Mesh-aware fused_add_layer_norm: rows are independent, so the
    kernel shards over dp rows with gamma/beta replicated.  (The hidden
    axis never shards in the decoder tables — LN reduces over it.)"""
    from jax.sharding import PartitionSpec as P

    from .pallas_kernels import _row_block, fused_add_layer_norm

    mc = mesh_ctx()
    if mc is None:
        return None
    mesh, rules, mp, nsh, dp_axis, ndp = mc
    row = _row_axis(dp_axis, ndp, x2.shape[0])
    if row is None:
        return None
    br = _row_block(x2.shape[0] // ndp, 256)

    def body(xl, yl, g, b):
        return fused_add_layer_norm(xl, yl, g, b, eps, br)

    rs = P(row, None)
    return _shard_map(mesh, body, (rs, rs, P(None), P(None)),
                      (rs, rs))(x2, y2, gamma, beta)


def spmd_flash_attention(mc, q, k, v, kbias_b, seg_b, causal, scale, bq, bk,
                         window):
    """Mesh-aware flash_attention (the training path): attention is
    independent across batch rows and heads, so the kernel runs per
    device with rows over dp and heads over mp, each wherever it divides
    (an axis that does not divide replicates — this form never
    declines).  mc: a live mesh_ctx().  q/k/v: rank-4 [B, H, Tq|Tk, D];
    kbias_b [B, Tk] and seg_b [B, T] are the PER-BATCH operands (or
    None), spread over the local heads inside the body."""
    from jax.sharding import PartitionSpec as P

    from .pallas_kernels import flash_attention

    mesh, _rules, mp, nsh, dp_axis, ndp = mc
    b, h = q.shape[:2]
    if v.shape[-1] != q.shape[-1]:
        raise ValueError(
            "spmd_flash_attention takes V at Q's width, got %d against %d"
            % (v.shape[-1], q.shape[-1]))
    rows = _row_axis(dp_axis, ndp, b)
    heads = mp if (nsh > 1 and h % nsh == 0) else None
    p4, p2 = P(rows, heads, None, None), P(rows, None)
    extras = [a for a in (kbias_b, seg_b) if a is not None]

    def body(q4, k4, v4, *extra):
        lb, lh, lt, ld = q4.shape
        ltk = k4.shape[2]

        def per_head(a):
            return jnp.broadcast_to(
                a[:, None, :], (lb, lh, a.shape[-1])).reshape(lb * lh, -1)

        extra = list(extra)
        kb = per_head(extra.pop(0)) if kbias_b is not None else None
        sg = per_head(extra.pop(0)) if seg_b is not None else None
        o = flash_attention(
            q4.reshape(lb * lh, lt, ld), k4.reshape(lb * lh, ltk, ld),
            v4.reshape(lb * lh, ltk, ld), kb, causal, scale, block_q=bq,
            block_k=bk, window=window, seg=sg)
        return o.reshape(lb, lh, lt, ld)

    return _shard_map(mesh, body, (p4, p4, p4) + (p2,) * len(extras),
                      p4)(q, k, v, *extras)
