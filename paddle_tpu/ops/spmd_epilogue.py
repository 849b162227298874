"""What an op's lowering does differently under a live GSPMD mesh.

``mesh_ctx`` says whether a step is being traced under one.
``pallas_call`` has no SPMD partition rule ("Mosaic kernels cannot be
automatically partitioned. Please wrap the call in a shard_map"), so the
one kernel a sharded step runs, ``flash_attention``, runs per device
inside ``shard_map`` (``spmd_flash_attention``: rows over dp, heads over
mp).  ``rule_sharded_weight`` / ``grad_in_param_storage`` keep a weight
whose rule names an axis that does not divide it computed in uneven
shards and stored replicated.
"""

import jax
import jax.numpy as jnp

__all__ = [
    "mesh_ctx", "rule_sharded_weight", "grad_in_param_storage",
    "spmd_flash_attention",
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


def _row_axis(dp_axis, ndp, rows):
    """The activation-rows mesh axis: the dp axis when it exists and
    divides the flattened row count, else None (rows replicate)."""
    return dp_axis if (dp_axis and ndp > 1 and rows % ndp == 0) else None


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

    from ..parallel.mesh import shard_map
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

    return shard_map(body, mesh=mesh,
                     in_specs=(p4, p4, p4) + (p2,) * len(extras),
                     out_specs=p4, check_vma=False)(q, k, v, *extras)
