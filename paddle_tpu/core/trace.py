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
import functools
import time

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

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


def state_format(name, sharding):
    """The format a step is compiled to take a donated read-write array
    in, and to hand back the result that aliases it: the sharding it has,
    the layout left to the compiler.  A value that is no array has no
    sharding (None) and gets no format.  One rule for every array of
    every program: what differs between programs is what the compiler
    answers for their shapes."""
    return None if sharding is None else Format(Layout.AUTO, sharding)


def jit_step(traced, shardings, feed_shardings=None, key_sharding=None,
             **options):
    """The jit of a traced step, the one every run path that compiles
    `traced.fn` for the device goes through (the flat path at a block's
    first call, Executor._jit_spmd_step, tools/compile_cell_for_chip.py):
    the read-write state donated and taken in `state_format`, and the
    result that aliases each array handed back in the same.  So the state
    LIVES in the layout the compiled step reads it in, where a default
    layout makes the compiler transpose it in and out of every step (a
    float32 [8, 2688, 1856] expert stack on a TPU: 1856 is 14.5 tiles of
    128 lanes, the default puts 2688 minor, and every consumer wants 1856
    minor).  Feeds, read-only state (other programs' executables share
    it), the rng key, fetches (they go to the host) and fresh outputs keep
    the default layout.

    A layout left to the compiler is known only once it has compiled, so
    the result is never called: `.lower(*avals).compile()` it
    (CompiledBlock does), lay the arrays out in `input_formats`, call the
    executable.  The jitted function returns (the read-write state's new
    values, fetches, every other updated variable).  Two dicts, because a
    format is given per result and `traced.fn` alone knows which updated
    names its trace produces; the read-write state first, because JAX
    pairs a donated argument with the first result of its shape and
    dtype, in order, and refuses a pair of which one side's layout is the
    compiler's and the other's is not: first, every array meets its own
    new value.  (A variable whose new value has another shape or dtype
    than the array it replaces cannot take its buffer: the array goes
    back as it came, and the new value goes with the fresh outputs.)

    `shardings`: {name: the sharding the array has} over
    `traced.rw_names`, and over the read-only and fresh state too where a
    mesh places them, as it gives `feed_shardings` and `key_sharding`
    (a name left out, or None: as the argument comes); `options`: further
    jax.jit arguments."""
    formats = {n: state_format(n, shardings[n]) for n in traced.rw_names}
    fresh = {n: shardings[n] for n in traced.updated
             if n not in formats and n in shardings}

    def aval(x):
        return getattr(x, "shape", None), getattr(x, "dtype", None)

    @functools.wraps(traced.fn)  # the module and every op_name keep its name
    def program_step(feeds, ro_state, rw_state, rng_key):
        fetches, new_state = traced.fn(feeds, ro_state, rw_state, rng_key)
        kept = {n: new_state.pop(n) if aval(new_state[n]) == aval(old) else old
                for n, old in rw_state.items()}
        return kept, fetches, new_state

    return jax.jit(
        program_step,
        in_shardings=(feed_shardings,
                      {n: shardings.get(n) for n in traced.ro_names},
                      formats, key_sharding),
        out_shardings=(formats, None, fresh or None),
        donate_argnums=(2,), **options)


class CompiledBlock:
    """One XLA executable for (program version, block, signature),
    compiled ahead of its first call (jit_step says why): that call
    lowers and compiles at the arguments' abstract signature, lays the
    scope's read-write arrays out once in the formats the compiler chose
    (a device_put of each array whose format differs; none for the
    rest), and runs; from then on the step's results come back in those
    formats and go in again unchanged.

    What the block compiled for is a signature as a jit's is, formats
    included.  Where JAX refuses a later call's arguments (it checks
    before it runs or donates anything), the block looks at what
    arrived: read-write arrays in another format (a checkpoint loaded
    into the scope) are laid out again; anything else that differs
    (another program's executable left a parameter this one reads in
    another layout, a shape changed) compiles again for what arrives, as
    a jit would for a new signature.

    An array is as good as its label (`relabelled` says what can go wrong
    with one and what the block does about it)."""

    def __init__(self, traced, feed_sig, jitted=None):
        self.traced = traced
        self.feed_sig = feed_sig
        # jit_step's result; None until the first call's arrays say where
        # the read-write state is placed
        self.jitted = jitted
        # the jax.stages.Compiled that runs, and how many were made
        self.executable = None
        self.compiles = 0
        # the trace_compile phase of the miss that made this block; the
        # first call resumes it (ExecutionCache.miss), a later compile
        # opens `again()`'s
        self.compiling = None
        self.again = None
        # abstract signature the executable was compiled at
        self.avals = None
        # {name: format} of the read-write results this executable hands
        # out under another format than it wrote them in (None: not looked
        # at yet; {} for an executable compiled in this process)
        self.mislabelled = None

    def __call__(self, feeds, ro_state, rw_state, rng_key, scope):
        args = (feeds, ro_state, rw_state, rng_key)
        if self.executable is None:
            self._compile(args)
            self._lay_out(rw_state, scope)
        try:
            kept, fetches, fresh = self.executable(*args)
        except (TypeError, ValueError):
            if not self._fit(args, scope):
                raise
            kept, fetches, fresh = self.executable(*args)
        if self.mislabelled is None:  # this executable's first results
            self.mislabelled = {
                n: written for n, written in self._written().items()
                if getattr(kept[n], "format", written) != written}
        if self.mislabelled:
            kept.update(relabelled({n: kept[n] for n in self.mislabelled},
                                   self.mislabelled))
        kept.update(fresh)
        return fetches, kept

    def _compile(self, args):
        if self.jitted is None:
            self.jitted = jit_step(
                self.traced, {n: getattr(a, "sharding", None)
                              for n, a in args[2].items()})
        self.avals = call_avals(args)
        self.executable = self.jitted.lower(*self.avals).compile()
        self.compiles += 1
        self.mislabelled = None

    def _written(self):
        """{name: format} the executable writes its read-write results in;
        {} for an executable that reports no layouts (one with host
        effects: JAX then compares none either)."""
        try:
            return self.executable.output_formats[0]
        except AssertionError:
            return {}

    def _lay_out(self, rw_state, scope):
        """Put every read-write array that is not in the format the
        executable takes it in into that format, in `rw_state` and in the
        scope (so that the array it replaces is freed before the step
        needs the room), and count them in the block's trace_compile
        record: `state_relayouts`, `state_relayout_s`."""
        t0 = time.perf_counter()
        # an argument the step never reads is pruned from the executable
        # and has no layout there: any array will do
        formats = {n: f for n, f in
                   self.executable.input_formats[0][2].items()
                   if getattr(f, "layout", f) is not None}
        moved = {}
        for n, f in formats.items():
            if getattr(rw_state[n], "format", f) != f:
                # one at a time, and waited for: the array it replaces is
                # let go of before the next copy asks for room
                moved[n] = jax.block_until_ready(
                    jax.device_put(rw_state[n], f))
                rw_state[n] = moved[n]
                scope.set(n, moved[n])
        for n, a in relabelled({n: a for n, a in moved.items()
                                if a.format != formats[n]}, formats).items():
            moved[n] = rw_state[n] = a
            scope.set(n, a)
        record = self.compiling.record["args"]
        record["state_relayouts"] = (record.get("state_relayouts", 0)
                                     + len(moved))
        record["state_relayout_s"] = (record.get("state_relayout_s", 0.0)
                                      + time.perf_counter() - t0)
        return moved

    def _fit(self, args, scope):
        """After JAX refused `args`: make executable and arguments agree.
        True where something was done about it."""
        compiled_at = self.avals
        if call_avals(args) != compiled_at:
            with self.again() as compiling:
                pass  # counted as a miss is, under a record of its own
            self.compiling = compiling
            with compiling:
                self._compile(args)
        return bool(self._lay_out(args[2], scope)
                    or self.avals is not compiled_at)


# {(name, shape, dtype, format), ...} -> the executable that relabels them
_RELABELLERS = {}


def relabelled(arrays, formats):
    """`arrays` ({name: array}) under the labels `formats[name]`: the SAME
    buffers, handed out as arrays that say the layout they are in.

    Why that is ever needed (JAX 0.9.0): an array that comes out of an
    executable READ FROM THE PERSISTENT COMPILATION CACHE says it is in
    the default layout, whatever layout the executable wrote it in.  (The
    runtime takes its results' layouts from the module's layout modes,
    and a deserialized executable has no module; the Python side's
    `output_formats` is right.)  The buffer is right and the label is
    wrong, and everything that takes the array at its word goes wrong
    with it: JAX refuses it to the executable that made it, and a jit
    that reads it reads garbage.  This repo's benchmark runs every
    process but the first warm, so what the compiler's layouts buy would
    be lost exactly where it is measured.

    The cure costs no copy: an identity over the arrays, each argument
    taken and handed back in its true format and donated (so aliased: the
    program moves nothing), compiled IN THIS PROCESS with the persistent
    cache out of the way (its own results would come mislabelled from
    there), and run on the runtime's executable directly, because JAX's
    own call would refuse the arguments for their labels.  {} in, {} out:
    where every label is right, nothing here runs."""
    if not arrays:
        return {}
    from jax.experimental.compilation_cache import compilation_cache

    names = sorted(arrays)
    key = tuple((n, arrays[n].shape, str(arrays[n].dtype), formats[n])
                for n in names)
    if key not in _RELABELLERS:
        true = {n: formats[n] for n in names}
        cached = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            _RELABELLERS[key] = jax.jit(
                lambda state: state, in_shardings=(true,),
                out_shardings=true, donate_argnums=(0,)).lower(
                    {n: jax.ShapeDtypeStruct(
                        arrays[n].shape, arrays[n].dtype,
                        sharding=formats[n].sharding) for n in names}
                ).compile().runtime_executable()
        finally:
            jax.config.update("jax_enable_compilation_cache", cached)
            compilation_cache.reset_cache()
    shards = _RELABELLERS[key].execute_sharded(
        [arrays[n] for n in names]).disassemble_into_single_device_arrays()
    # an array on one device is its one shard; over a mesh, the shards
    # are put together again under the sharding they came in
    return {n: of_one[0] if len(arrays[n].sharding.device_set) == 1
            else jax.make_array_from_single_device_arrays(
                arrays[n].shape, formats[n].sharding, of_one)
            for n, of_one in zip(names, shards)}


def sig_text(feed_sig):
    """A feed signature ((name, shape, dtype), ...) as one short string:
    the argument of a trace_compile phase, so that a recompile names its
    cause in the trace and in the set-up ledger."""
    return " ".join("%s:%s%s" % (n, dt, list(shape))
                    for n, shape, dt in feed_sig)


def call_avals(args):
    """Abstract twin of a step's arguments (feeds, ro_state, rw_state, rng
    key): what CompiledBlock lowers at, and the signature it compiled
    for.  A read-only array keeps its whole format: a parameter that
    another program's executable laid out is read in the layout it
    arrives in, as a jit with no layout given reads it.  A read-write
    array keeps its sharding alone: its layout is jit_step's to leave to
    the compiler.  An uncommitted array (the per-step rng key) keeps no
    sharding: pinning it would change the lowered module, and with it the
    compilation-cache key."""
    def twin(placement):
        def aval(x):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, weak_type=getattr(x, "weak_type", False),
                sharding=(getattr(x, placement)
                          if getattr(x, "committed", True) else None))
        return aval

    feeds, ro_state, rw_state, rng_key = args
    tree_map = jax.tree_util.tree_map
    return (tree_map(twin("sharding"), feeds),
            tree_map(twin("format"), ro_state),
            tree_map(twin("sharding"), rw_state),
            twin("sharding")(rng_key))


class ExecutionCache:
    """Compile cache keyed by (program id, version, feed signature) — the
    analog of Executor::Prepare context reuse + XLA executable caching."""

    def __init__(self):
        self._cache = {}
        # monotone count of cache MISSES (fresh traces) — the serving
        # engine's compiles-once contract is asserted against this:
        # occupancy churn must change feed VALUES only, never keys
        self.compile_count = 0

    def get(self, program, block_idx, feed_sig, fetch_names, scope,
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
            compiled = CompiledBlock(traced, feed_sig)
        compiled.compiling = compiling
        compiled.again = lambda: self.miss(program, feed_sig, "flat")
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
