"""Block tracer + XLA compile cache — the heart of the execution engine.

This replaces the reference's interpreting ``Executor``
(``paddle/fluid/framework/executor.cc:380`` hot loop: per-op InferShape +
kernel dispatch) with a compile-first design: a Block's op sequence is traced
symbolically through the op lowering rules into a single pure JAX function

    f(feeds, ro_state, rw_state, rng_key) -> (fetches, new_state)

which ``jax.jit`` compiles once per (program version, input signature) and
caches — Executor::Prepare + the kernel loop collapsing into one XLA
executable.  Scope mutation (parameter updates, BN running stats, optimizer
state) is functionalized: every scope variable an op writes becomes an output
threaded back into the scope after the step.  ``rw_state`` (read+written
vars — parameters under training) is donated, so updates alias in HBM; pure
reads (``ro_state``) are not donated and stay valid across steps.
"""

import contextlib

import jax
import jax.numpy as jnp

from ..profiler import phase
from .registry import OPS, LowerCtx, get_op, lower_grad_op
from .selected_rows import SelectedRows, densify_maybe


class _TraceContextError(RuntimeError):
    """Lowering failure annotated with op/block/shape context
    (PADDLE_ENFORCE error-context discipline, platform/enforce.h)."""


class TracedFunction:
    def __init__(self, fn, feed_names, ro_names, rw_names, fetch_names, updated,
                 stat_names=()):
        self.fn = fn
        self.feed_names = feed_names
        self.ro_names = ro_names
        self.rw_names = rw_names
        self.fetch_names = fetch_names
        self.updated = updated
        # the step statistics whose history the Executor keeps
        # (step_stat_names): a tuple, empty for most programs
        self.stat_names = stat_names


def step_stat_names(block, keep, updated, rw_names):
    """The step statistics of a traced block that are a FRESH output of
    every step: the persistable variables in a slot some kept op's
    registration declares (OpDef.stat_outputs) that the step writes
    (`updated`) and does not read first (not in `rw_names`).  Such an
    output is never donated, so the array of an earlier step stays
    readable for as long as someone holds it: Executor._commit does.  A
    statistic the step reads before it writes (an accumulator) is donated
    to the next step and is left out: its earlier arrays are deleted."""
    fresh = set(updated) - set(rw_names)
    names = []
    for op, kept in zip(block.ops, keep):
        if not kept or op.type not in OPS:
            continue
        for slot in OPS[op.type].stat_outputs:
            names.extend(n for n in op.outputs.get(slot, ())
                         if n in fresh and n not in names)
    return tuple(names)


def dce_mask(program, block_idx, fetch_names):
    """Dead-code elimination: keep ops reachable from the fetch targets or
    writing persistable state (optimizer updates, BN stats, counters run
    unconditionally, matching interpreter side-effect semantics).  The
    analog of Program pruning (prune.cc) done implicitly per execution."""
    blk = program.block(block_idx)

    def is_persistable(name):
        v = blk._find_var_recursive(name)
        return v is not None and v.persistable

    # test-mode programs (clone(for_test=True)) never run training-only
    # ops, even though those write persistable state (fluid semantics:
    # Program.clone strips nothing, but an is_test run must not step the
    # optimizer or touch grads)
    is_test = getattr(program, "_is_test", False)
    train_roles = ("backward", "optimize", "lrsched", "loss", "rpc")

    needed = set(fetch_names)
    keep = [False] * len(blk.ops)
    for i in range(len(blk.ops) - 1, -1, -1):
        op = blk.ops[i]
        if is_test and op.attrs.get("op_role") in train_roles:
            continue
        outs = op.output_arg_names()
        opdef = OPS.get(op.type)
        if (
            any(n in needed for n in outs)
            or any(is_persistable(n) for n in outs)
            or (opdef is not None and opdef.side_effect)
        ):
            keep[i] = True
            needed.update(op.input_arg_names())
    return keep


def op_sub_blocks(op):
    """Sub-block indices owned by an op — THE discovery primitive every
    block analyzer shares (visit_reads_writes, the IfElse branch-effect
    guard): any `sub_block*` attr, int-valued (while/cond/recurrent) or
    list-valued (switch's sub_block_idxs)."""
    out = []
    for a, v in op.attrs.items():
        if not a.startswith("sub_block"):
            continue
        if isinstance(v, int):
            out.append(v)
        elif isinstance(v, (list, tuple)):
            out.extend(int(i) for i in v)
    return out


def visit_reads_writes(program, bidx, defined, on_read, on_write=None, pre_op=None):
    """Shared block traversal: report names read before being written
    (recursing into sub_block attrs, whose `__bound_names__` — recurrent
    step slices, carried loop state — are defined by the op's lowering,
    not external reads).  `pre_op(bidx, i, op)` may return "skip" to drop
    an op or "define" to treat its outputs as given (feed/read ops)."""
    blk = program.block(bidx)
    for i, op in enumerate(blk.ops):
        if pre_op is not None:
            action = pre_op(bidx, i, op)
            if action == "skip":
                continue
            if action == "define":
                for n in op.output_arg_names():
                    defined.add(n)
                continue
        for name in op.input_arg_names():
            if name and name not in defined:
                on_read(name)
        for sub_idx in op_sub_blocks(op):
            bound = op.attrs.get("__bound_names__", ())
            visit_reads_writes(
                program, sub_idx, set(defined) | set(bound), on_read,
                on_write, pre_op
            )
        for name in op.output_arg_names():
            defined.add(name)
            if on_write is not None:
                on_write(name)


def sub_block_external_reads(program, block, bound):
    """Outer-scope names a sub-block (incl. nested) reads before writing —
    what a sub-block-owning op must declare as inputs (layer-build-time
    counterpart of analyze_block's trace-time discovery)."""
    reads = []
    seen = set()

    def on_read(n):
        if n not in seen:
            seen.add(n)
            reads.append(n)

    visit_reads_writes(program, block.idx, set(bound), on_read)
    return reads


def analyze_block(program, block_idx, feed_names, fetch_names, keep=None):
    """Find external reads (scope state the block consumes) and all writes,
    across the block and its sub-blocks."""
    reads = []
    reads_set = set()
    writes = []
    writes_set = set()

    def on_read(name):
        if name not in reads_set:
            reads_set.add(name)
            reads.append(name)

    def on_write(name):
        if name not in writes_set:
            writes_set.add(name)
            writes.append(name)

    def pre_op(bidx, i, op):
        if keep is not None and bidx == block_idx and not keep[i]:
            return "skip"
        if op.type in ("feed", "read"):
            # read-op outputs arrive as implicit feeds (executor pops the
            # reader queue); the Reader var itself is host state
            return "define"
        return None

    visit_reads_writes(
        program, block_idx, set(feed_names), on_read, on_write, pre_op
    )
    for n in fetch_names:
        if n not in writes_set and n not in set(feed_names) and n not in reads_set:
            reads_set.add(n)
            reads.append(n)
    return reads, writes


def build_traced_function(program, block_idx, feed_names, fetch_names, scope,
                          collective_axis=None, spmd=None, keep=None,
                          platform=None):
    """`collective_axis`: optional ("axis_name", nranks) pair binding the
    collective-lowering context around the trace — c_allreduce_* ops then
    lower to jax.lax collectives over that axis instead of identity.  The
    caller (executor._run_collective) is responsible for actually running
    the traced fn under a shard_map that binds the axis.

    `spmd`: optional (mesh, PartitionRules) pair binding the GSPMD
    lowering context (parallel.partition_rules.spmd_lowering) around the
    trace — mesh-aware lowerings (fused_attention's vector-QStart
    branch, slot_cache_write) then emit shard_map-wrapped kernels /
    sharding constraints.  The caller (executor._run_spmd) jits the
    traced fn with the rule table's in/out shardings.

    `keep`: optional explicit per-op keep mask for `block_idx`, replacing
    the internal DCE mask.  Pipeline stage slicing passes its own masks so
    a stage traces exactly its op range — DCE would otherwise drag the
    whole optimizer chain in through persistable writes.

    `platform`: the platform of the device(s) the caller will run the
    step on (LowerCtx.platform: lowerings that pick a kernel by platform
    read it)."""
    if keep is None:
        keep = dce_mask(program, block_idx, fetch_names)
    reads, writes = analyze_block(program, block_idx, feed_names, fetch_names, keep)
    state_names = [n for n in reads if scope.has_var(n)]
    missing = [n for n in reads if not scope.has_var(n)]
    if missing:
        raise RuntimeError(
            "variables %s are read by the program but neither fed nor found in "
            "scope — run the startup program first" % missing
        )
    block = program.block(block_idx)

    def is_persistable(name):
        v = block._find_var_recursive(name)
        return v is not None and v.persistable

    state_set = set(state_names)
    # updated = state that is rewritten, plus fresh persistable writes
    # (optimizer accumulators created mid-program)
    updated = [n for n in writes if n in state_set or is_persistable(n)]
    rw_names = [n for n in state_names if n in set(updated)]
    ro_names = [n for n in state_names if n not in set(updated)]
    is_test = getattr(program, "_is_test", False)

    def program_step(feeds, ro_state, rw_state, rng_key):
        if collective_axis is not None:
            from ..parallel.collective import collective_lowering

            with collective_lowering(*collective_axis):
                return _fn_body(feeds, ro_state, rw_state, rng_key)
        if spmd is not None:
            from ..parallel.partition_rules import spmd_lowering

            with spmd_lowering(*spmd):
                return _fn_body(feeds, ro_state, rw_state, rng_key)
        return _fn_body(feeds, ro_state, rw_state, rng_key)

    def _fn_body(feeds, ro_state, rw_state, rng_key):
        env = {}
        env.update(ro_state)
        env.update(rw_state)
        env.update(feeds)
        ctx = LowerCtx(rng_key=rng_key, is_test=is_test, scope=scope,
                       platform=platform)

        def trace_while(op, env):
            """Lower a `while` op to lax.while_loop (while_op.cc:36 analog:
            the sub-block interpreter + StepScopes collapse into compiled
            XLA control flow).  Loop state = the op's carried_vars; the
            condition var must be recomputed inside the body (fluid's
            `layers.less_than(..., cond=cond)` idiom ensures this)."""
            sub_idx = op.attrs["sub_block_idx"]
            carried = list(op.attrs["carried_vars"])
            cond_name = op.inputs["Condition"][0]
            if cond_name not in carried:
                raise RuntimeError(
                    "While condition var '%s' is not updated in the loop body "
                    "(infinite loop); recompute it with layers.less_than(..., "
                    "cond=cond)" % cond_name
                )

            def cond_fn(carry):
                return jnp.reshape(carry[carried.index(cond_name)], ()).astype(bool)

            def body_fn(carry):
                env2 = dict(env)
                env2.update(zip(carried, carry))
                env2 = trace_ops(sub_idx, env2)
                return tuple(env2[n] for n in carried)

            init = tuple(env[n] for n in carried)
            out = jax.lax.while_loop(cond_fn, body_fn, init)
            env.update(zip(carried, out))
            return env

        def trace_cond(op, env):
            """Lower a `cond` op to lax.cond; branch sub-blocks close over
            the outer env, outputs are the declared branch result vars."""
            pred = jnp.reshape(env[op.inputs["Condition"][0]], ()).astype(bool)
            tidx = op.attrs["sub_block_true_idx"]
            fidx = op.attrs["sub_block_false_idx"]
            touts = op.attrs["true_outs"]
            fouts = op.attrs["false_outs"]

            def tf(_):
                return tuple(trace_ops(tidx, dict(env))[n] for n in touts)

            def ff(_):
                return tuple(trace_ops(fidx, dict(env))[n] for n in fouts)

            outs = jax.lax.cond(pred, tf, ff, None)
            for n, v in zip(op.outputs["Out"], outs):
                env[n] = v
            return env

        # pre-execution input snapshots for ops that overwrite their own
        # inputs (loop carries, assign-into-existing): their grad ops re-run
        # the forward lowering and MUST see the original inputs, not the
        # post-op values the in-place write left in env
        snapshots = {}

        def trace_ops(bidx, env):
            """Lower a block's ops in order, each under the named scope
            `<op_role>/<op type>/<index in its block>`: the scope reaches
            the optimized HLO's `op_name` and the device trace, so a
            fused instruction can be traced back to the Fluid ops in it.
            Sub-block ops nest under their parent op's scope.  An op built
            under `fluid.name_scope`s carries them as one more nested part
            of the same form, `<op_role>/<the scopes joined by ".">/<how
            many>` ("forward/fc/12/forward/ut2.layer0/2"), so whatever
            reads `<role>/<type>/<index>` parts reads this one too; an op
            built under none keeps the path it had.  Scopes act at trace
            time only: a compiled step pays nothing for them."""
            blk = program.block(bidx)
            for idx, op in enumerate(blk.ops):
                if op.type in ("feed", "fetch", "read", "create_py_reader"):
                    continue  # satisfied as implicit feeds / host state
                if bidx == block_idx and not keep[idx]:
                    continue
                role = op.attrs.get("op_role", "forward")
                path = "%s/%s/%d" % (role, op.type, idx)
                built_under = op.attrs.get("op_namescope")
                if built_under:
                    parts = built_under.split("/")
                    path += "/%s/%s/%d" % (role, ".".join(parts), len(parts))
                with jax.named_scope(path):
                    env = trace_op(blk, bidx, idx, op, env)
            return env

        def trace_op(blk, bidx, idx, op, env):
            ctx.op_idx = (bidx << 20) | idx
            ctx.block = blk
            if op.type == "while":
                return trace_while(op, env)
            if op.type == "cond":
                return trace_cond(op, env)
            is_grad = op.type.endswith("_grad") and "__fwd_type__" in op.attrs
            snap = None
            if is_grad:
                snap = snapshots.get((bidx, op.attrs.get("__fwd_op_idx__")))
            elif set(op.output_arg_names()) & set(op.input_arg_names()):
                snapshots[(bidx, idx)] = {
                    n: env[n] for n in op.input_arg_names() if n in env
                }
            ins = {}
            for slot, names in op.inputs.items():
                vals = []
                use_snap = snap if not slot.endswith("@GRAD") else None
                for n in names:
                    if use_snap is not None and n in use_snap:
                        vals.append(use_snap[n])
                        continue
                    if n not in env:
                        raise RuntimeError(
                            "op %s reads undefined var %s" % (op.type, n)
                        )
                    vals.append(env[n])
                ins[slot] = vals
            try:
                opdef = OPS.get(op.type)
                # SelectedRows inputs densify automatically for ops that
                # don't declare native support (reference: kernels not
                # specialized on SELECTED_ROWS see a dense tensor)
                if any(
                    isinstance(v, SelectedRows)
                    for vals in ins.values() for v in vals
                ) and not (opdef is not None
                           and opdef.handles_selected_rows):
                    ins = {
                        s: [densify_maybe(v) for v in vals]
                        for s, vals in ins.items()
                    }
                if spmd is not None:
                    from ..ops.spmd_epilogue import grad_in_param_storage

                    ins = grad_in_param_storage(op, ins)
                if opdef is not None:
                    outs = opdef.lower(ctx, ins, op.attrs)
                elif is_grad:
                    outs = lower_grad_op(ctx, op, ins, op.attrs)
                else:
                    outs = get_op(op.type).lower(ctx, ins, op.attrs)
            except Exception as e:
                # PADDLE_ENFORCE-style error context (enforce.h): name
                # the op and its inputs so a shape/dtype error inside a
                # compiled block is attributable without reading XLA
                # internals.  Tracer-context errors pass through.
                if isinstance(e, _TraceContextError):
                    raise
                shapes = {
                    slot: [getattr(v, "shape", "?") for v in vals]
                    for slot, vals in ins.items()
                }
                raise _TraceContextError(
                    "while lowering op '%s' (block %d, op %d) with input "
                    "shapes %s: %s: %s"
                    % (op.type, bidx, idx, shapes, type(e).__name__, e)
                ) from e
            for slot, names in op.outputs.items():
                vals = outs.get(slot)
                if vals is None:
                    continue
                for n, v in zip(names, vals):
                    if n and v is not None:
                        env[n] = v
            return env

        ctx.trace_block = trace_ops
        env = trace_ops(block_idx, env)

        fetches = []
        for n in fetch_names:
            if n not in env:
                raise RuntimeError("fetch var %s was never produced" % n)
            fetches.append(densify_maybe(env[n]))
        new_state = {n: densify_maybe(env[n]) for n in updated if n in env}
        return fetches, new_state

    return TracedFunction(program_step, list(feed_names), ro_names, rw_names,
                          fetch_names, updated,
                          step_stat_names(block, keep, updated, rw_names))


class CompiledBlock:
    """One XLA executable for (program version, block, signature)."""

    def __init__(self, traced, jitted, feed_sig):
        self.traced = traced
        self.jitted = jitted
        self.feed_sig = feed_sig
        # the trace_compile phase of the miss that made this block; the
        # first call resumes it (ExecutionCache.miss)
        self.compiling = None
        # abstract signature of the first call, so Executor.compiled_hlo
        # can AOT-lower the same executable later
        self.avals = None

    def __call__(self, feeds, ro_state, rw_state, rng_key):
        if self.avals is None:
            self.avals = call_avals((feeds, ro_state, rw_state, rng_key))
        return self.jitted(feeds, ro_state, rw_state, rng_key)


def sig_text(feed_sig):
    """A feed signature ((name, shape, dtype), ...) as one short string:
    the argument of a trace_compile phase, so that a recompile names its
    cause in the trace and in the set-up ledger."""
    return " ".join("%s:%s%s" % (n, dt, list(shape))
                    for n, shape, dt in feed_sig)


def call_avals(args):
    """Abstract twin of a call's arguments.  An uncommitted array (the
    per-step rng key) keeps no sharding: pinning it would change the
    lowered module, and with it the compilation-cache key, so the AOT
    compile would miss the entry the jit call just wrote."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=x.sharding if getattr(x, "committed", True) else None),
        args)


class ExecutionCache:
    """Compile cache keyed by (program id, version, feed signature) — the
    analog of Executor::Prepare context reuse + XLA executable caching."""

    def __init__(self):
        self._cache = {}
        # monotone count of cache MISSES (fresh traces) — the serving
        # engine's compiles-once contract is asserted against this:
        # occupancy churn must change feed VALUES only, never keys
        self.compile_count = 0

    def get(self, program, block_idx, feed_sig, fetch_names, scope, donate=True,
            platform=None):
        key = (
            id(program),
            program._version,
            block_idx,
            feed_sig,
            tuple(fetch_names),
            id(scope),
        )
        hit = self._cache.get(key)
        if hit is not None:
            return hit
        with self.miss(program, feed_sig, "flat") as compiling:
            feed_names = tuple(n for n, _, _ in feed_sig)
            traced = build_traced_function(
                program, block_idx, feed_names, fetch_names, scope,
                platform=platform)
            jitted = jax.jit(traced.fn,
                             donate_argnums=(2,) if donate else ())
            compiled = CompiledBlock(traced, jitted, feed_sig)
        compiled.compiling = compiling
        self._cache[key] = compiled
        return compiled

    @contextlib.contextmanager
    def miss(self, program, feed_sig, path):
        """A cache miss of any run path (the Executor's mesh paths keep
        their own tables and come here too): counted in compile_count,
        and under it the `trace_compile` phase the new executable's two
        spans share.  The first, here, is the block's analysis
        (`analyse_s`); tracing, lowering and compiling wait for the
        executable's first call, which the Executor runs under the same
        phase, resumed (Executor._dispatch): one record in
        profiler.phases() an executable, naming the feed signature, the
        program (its id) and the path, with what JAX reports of the
        inside of the compile (profiler._COMPILE_SPANS)."""
        self.compile_count += 1
        compiling = phase("trace_compile", feed_sig=sig_text(feed_sig),
                          program=id(program), path=path)
        with compiling:
            yield compiling
        record = compiling.record
        record["args"]["analyse_s"] = record["t1"] - record["t0"]

    def blocks_for(self, program):
        """Every CompiledBlock cached for `program`."""
        return [cb for key, cb in self._cache.items()
                if key[0] == id(program)]

    def clear(self):
        self._cache.clear()
