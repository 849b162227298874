"""Pipeline-parallel training: stage-sliced programs + microbatch schedules.

``pipeline_program(program, mesh, ...)`` slices a BUILT train program (fwd +
backward + optimizer ops already appended) into S stage sub-programs at
activation-frontier cut points — ``detect_segments`` waists generalized by
one extra live tensor, so pre-LN residual-stream layer boundaries qualify —
balanced by the same per-activation byte model remat's estimator uses, and
drives a GPipe or 1F1B microbatch schedule as one ``lax.scan`` inside
``shard_map`` over a dp×mp×pp mesh.

Slicing contract
----------------
- Only the FORWARD region (ops before the first backward/optimize/lrsched op)
  is sliced.  Stage gradients come from ``jax.vjp``/``jax.value_and_grad`` of
  the traced stage forward — numerically the same math as the program's
  backward ops, which backward.py itself lowers through ``jax.vjp`` of the
  forward rules.
- The program's OPTIMIZER ops are reused verbatim: each stage re-traces the
  adam (+lr-schedule) ops owning its params, with the AD gradients fed under
  each op's declared Grad input name.  ``TrainPartitionRules`` stage-scoped
  resolution (``StageResolution``) assigns every derived name — grads, Adam
  moments, beta-pow accumulators, bf16 cast mirrors — to its param's stage.
- Per-stage params + optimizer state pack into flat per-dtype buffers of
  shape [S, L] sharded ``P(pp)`` (the ``stack_stage_params`` discipline from
  parallel/pipeline.py lifted to ragged stages via per-stage layouts), so
  per-device state bytes are the max stage's, not the sum.
- Activations hop stage→stage over ``lax.ppermute``; heterogeneous stage
  boundaries ride one union carry dict (every boundary name, shapes fixed by
  ``jax.eval_shape``), and ``lax.switch`` on ``lax.axis_index(pp)`` picks the
  device's stage body.
- dp shards the batch (feeds split over dp; grads psum over dp); mp axes are
  carried through replicated within a stage in this revision.

Exactness: pp=1 returns the program untouched (bit-identical path); pp>=2
matches the unpipelined program at rtol<=1e-5 (same per-step RNG key, same
per-op fold-in indices — the keep-mask slice preserves op positions, and
dropout draws its mask over the full global batch rows via the
``microbatch_rows`` context so microbatching never changes the mask).

Schedules: "gpipe" runs all M forwards then one backward through the scanned
schedule (O(M) activation residency via the scan's stacked residuals);
"1f1b" interleaves, stashing at most 2S-1 in-flight stage inputs (O(S)
residency) and re-deriving each microbatch's backward with a per-tick
``jax.vjp``.
"""

import itertools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.registry import microbatch_rows
from ..core.trace import build_traced_function
from ..parallel.mesh import mesh_axis_sizes, pcast_varying, shard_map
from ..parallel.partition_rules import StageResolution, TrainPartitionRules
from .remat import (
    _activation_bytes,
    _is_activation,
    _op_reads,
    pin_rng_streams,
)

__all__ = [
    "PipelinePlan",
    "build_pipeline_plan",
    "pipeline_program",
    "pipeline_activation_report",
    "pipeline_state_report",
]

_BWD_ROLES = ("backward", "optimize", "lrsched", "rpc")
_SCHEDULES = ("gpipe", "1f1b")


# ---------------------------------------------------------------------------
# plan: the static slice of the program into stages
# ---------------------------------------------------------------------------
class PipelinePlan:
    """Static stage slicing of one train program.  Everything here is
    derivable from the program alone (no scope, no shapes beyond the
    batch_hint byte model), so the executor can build/verify against it
    and the verifier can diagnose it without running anything."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def describe(self):
        lines = []
        for s, (lo, hi) in enumerate(self.stage_ranges):
            lines.append(
                "stage %d: ops[%d:%d) params=%d state_bytes=%d "
                "boundary_in=%s" % (
                    s, lo, hi, len(self.stage_params[s]),
                    self.state_bytes[s], self.boundary_in[s]))
        return "\n".join(lines)


def _forward_end(ops):
    for i, op in enumerate(ops):
        if op.attrs.get("op_role") in _BWD_ROLES:
            return i
    return len(ops)


def _find_loss_name(ops, fwd_end):
    """The backward seed op (backward.py: fill_constant of ones into
    <loss>@GRAD) names the loss."""
    for op in ops[fwd_end:]:
        if op.attrs.get("op_role") != "backward":
            continue
        if op.type != "fill_constant":
            continue
        outs = op.output_arg_names()
        if len(outs) == 1 and outs[0].endswith("@GRAD"):
            return outs[0][: -len("@GRAD")]
    return None


def _var_bytes(block, name, batch_hint):
    return _activation_bytes(block, name, batch_hint)


def _cut_candidates(program, block, fwd_end, max_frontier=2):
    """Forward op boundaries legal as stage cuts: the live activation
    frontier (non-persistable names defined before the boundary and read
    at/after it, within the forward region) holds at most `max_frontier`
    tensors.  ``detect_segments`` waists are exactly the frontier==1
    subset; admitting one more live tensor covers the pre-LN residual
    stream (residual + branch value), so transformer LAYER boundaries
    become cut points even though the residual keeps any single-tensor
    waist from forming there.  The union boundary carry hops every live
    name, so a multi-tensor cut costs hop bytes, not correctness."""
    ops = block.ops
    later_at = [set() for _ in range(fwd_end + 1)]
    for i in range(fwd_end - 1, -1, -1):
        later_at[i] = later_at[i + 1] | set(_op_reads(program, ops[i]))
    cuts = []
    defined = set()
    for b in range(1, fwd_end):
        defined.update(n for n in ops[b - 1].output_arg_names() if n)
        live = sum(1 for n in defined & later_at[b]
                   if _is_activation(block, n))
        if live <= max_frontier:
            cuts.append(b)
    return cuts


def _balance_stages(program, block, fwd_end, n_stages, batch_hint):
    """Partition the forward region into n_stages ranges over the legal
    cut points, minimizing the max per-stage activation bytes (primary —
    the estimator-balanced contract), tie-broken on max per-stage
    param+optimizer-state bytes (what bounds per-device HBM for the
    packed state buffers)."""
    ops = block.ops
    op_act = [0] * fwd_end
    op_state = [0] * fwd_end
    act_seen = set()
    params_seen = set()
    for i in range(fwd_end):
        for nm in ops[i].output_arg_names():
            if nm and nm not in act_seen and _is_activation(block, nm):
                act_seen.add(nm)
                op_act[i] += _var_bytes(block, nm, batch_hint)
        for nm in _op_reads(program, ops[i]):
            v = block._find_var_recursive(nm)
            if v is not None and v.persistable and nm not in params_seen:
                params_seen.add(nm)
                # param + two Adam moments (beta pows are scalars)
                op_state[i] += 3 * _var_bytes(block, nm, batch_hint)
    pa = [0] * (fwd_end + 1)
    ps = [0] * (fwd_end + 1)
    for i in range(fwd_end):
        pa[i + 1] = pa[i] + op_act[i]
        ps[i + 1] = ps[i] + op_state[i]

    cuts = _cut_candidates(program, block, fwd_end)
    if len(cuts) < n_stages - 1:
        raise ValueError(
            "program has only %d legal stage cut points (activation "
            "frontier <= 2) in its forward region — cannot slice into "
            "%d pipeline stages" % (len(cuts), n_stages))

    # keep enumeration tractable: drop the cut bordering the least
    # activation mass until the combination space is small
    while math.comb(len(cuts), n_stages - 1) > 100000:
        bounds = [0] + cuts + [fwd_end]
        k = min(range(1, len(bounds) - 1),
                key=lambda i: pa[bounds[i + 1]] - pa[bounds[i - 1]])
        del cuts[k - 1]

    best = None
    for comb in itertools.combinations(cuts, n_stages - 1):
        bounds = (0,) + comb + (fwd_end,)
        acts = [pa[b] - pa[a] for a, b in zip(bounds, bounds[1:])]
        states = [ps[b] - ps[a] for a, b in zip(bounds, bounds[1:])]
        key = (max(acts), max(states))
        if best is None or key < best[0]:
            best = (key, bounds)
    bounds = best[1]
    return [(a, b) for a, b in zip(bounds, bounds[1:])]


def build_pipeline_plan(program, n_stages, n_microbatches, schedule,
                        pp_axis="pp", dp_axis="dp", batch_hint=8,
                        stage_ranges=None):
    """Slice `program` into `n_stages` forward stages + per-stage optimizer
    slices.  `stage_ranges` overrides the balanced partition with explicit
    (lo, hi) forward op ranges — the verifier's mis-slice tests use this."""
    if schedule not in _SCHEDULES:
        raise ValueError("schedule must be one of %s, got %r"
                         % (_SCHEDULES, schedule))
    block = program.block(0)
    ops = block.ops
    n_ops = len(ops)
    fwd_end = _forward_end(ops)
    loss_name = _find_loss_name(ops, fwd_end)
    if loss_name is None:
        raise ValueError(
            "pipeline_program needs a built TRAIN program (append_backward "
            "ran): no loss-grad seed op found after op %d" % fwd_end)

    if stage_ranges is None:
        stage_ranges = _balance_stages(program, block, fwd_end,
                                       n_stages, batch_hint)
    else:
        stage_ranges = [tuple(r) for r in stage_ranges]

    # --- per-stage read/write sets over the forward region
    defined = []
    reads = []
    data_feeds = []
    fwd_persist = []
    for lo, hi in stage_ranges:
        d = set()
        r = set()
        dat = set()
        per = set()
        for op in ops[lo:hi]:
            for nm in _op_reads(program, op):
                if not nm:
                    continue
                v = block._find_var_recursive(nm)
                if v is None:
                    continue
                if v.persistable:
                    per.add(nm)
                elif getattr(v, "is_data", False):
                    dat.add(nm)
                else:
                    r.add(nm)
            for nm in op.output_arg_names():
                if nm:
                    d.add(nm)
        defined.append(d)
        reads.append(r)
        data_feeds.append(sorted(dat))
        fwd_persist.append(per)

    # params read by more than one forward stage cannot be stage-owned
    # (tied embeddings would need a grad cross-hop)
    owner = {}
    for s, per in enumerate(fwd_persist):
        for nm in per:
            if nm in owner and owner[nm] != s:
                raise NotImplementedError(
                    "param %r is read by pipeline stages %d and %d — "
                    "cross-stage weight sharing (tied embeddings) is not "
                    "supported; rebuild with tie_embeddings=False or "
                    "adjust the slicing" % (nm, owner[nm], s))
            owner.setdefault(nm, s)
    stage_params = [sorted(n for n, s in owner.items() if s == s_i)
                    for s_i in range(n_stages)]
    resolution = StageResolution(owner, n_stages)

    # --- boundary hops: what each stage must receive / forward along
    boundary_in = [sorted(r - d) for r, d in zip(reads, defined)]
    later_reads = [set() for _ in range(n_stages)]
    acc = set()
    for s in range(n_stages - 1, -1, -1):
        later_reads[s] = set(acc)
        acc |= reads[s]
    boundary_out = []
    avail = set()
    for s in range(n_stages):
        avail = (avail | defined[s])
        boundary_out.append(sorted(avail & later_reads[s]))
        avail = set(boundary_out[s])

    if loss_name not in defined[-1]:
        raise ValueError(
            "loss %r is not computed by the last pipeline stage (ranges "
            "%s) — the slicer must keep the loss head in stage S-1"
            % (loss_name, stage_ranges))

    stage_feed_names = []
    for s in range(n_stages):
        hop = boundary_out[s - 1] if s > 0 else []
        stage_feed_names.append(list(hop) + list(data_feeds[s]))

    # --- forward keep masks
    fwd_masks = []
    for lo, hi in stage_ranges:
        fwd_masks.append([lo <= i < hi for i in range(n_ops)])

    # --- optimizer region: assign each kept op to a stage (or all stages)
    opt_sets = [set() for _ in range(n_stages)]
    all_stage_ops = set()
    for i in range(fwd_end, n_ops):
        op = ops[i]
        role = op.attrs.get("op_role")
        if role == "backward":
            continue  # replaced by AD of the stage forward
        if role == "rpc":
            raise NotImplementedError(
                "pipeline_program cannot slice rpc ops (op %d)" % i)
        names = set(_op_reads(program, op)) | set(op.output_arg_names())
        stages = {resolution.stage_for(nm) for nm in names}
        stages.discard(None)
        if not stages or role == "lrsched":
            # pure lr-schedule / shared-state ops replicate into every
            # stage slice (each device steps its own copy of the shared
            # counters — identical values everywhere)
            all_stage_ops.add(i)
        elif len(stages) == 1:
            opt_sets[stages.pop()].add(i)
        else:
            raise NotImplementedError(
                "optimizer op %d (%s) touches params of stages %s — "
                "cross-stage optimizer ops (e.g. global-norm clip) are "
                "not supported under pipeline slicing"
                % (i, op.type, sorted(stages)))
    opt_masks = []
    for s in range(n_stages):
        kept = opt_sets[s] | all_stage_ops
        opt_masks.append([i in kept for i in range(n_ops)])

    # --- per-stage optimizer feeds: grad roots -> owning param
    grad_feed_param = []
    opt_persist = [set() for _ in range(n_stages)]
    shared_persist = set()
    for s in range(n_stages):
        kept = sorted(opt_sets[s] | all_stage_ops)
        written = set()
        for i in kept:
            written |= set(ops[i].output_arg_names())
        gmap = {}
        for i in kept:
            for nm in _op_reads(program, ops[i]):
                if not nm:
                    continue
                v = block._find_var_recursive(nm)
                if v is not None and v.persistable:
                    st = resolution.stage_for(nm)
                    if st == s:
                        opt_persist[s].add(nm)
                    elif st is None:
                        shared_persist.add(nm)
                    continue
                if nm in written:
                    continue
                base = resolution.base_name(nm)
                if base not in owner:
                    raise NotImplementedError(
                        "optimizer op %d reads %r, which is neither "
                        "produced by the stage-%d optimizer slice nor a "
                        "gradient of a stage-%d param" % (i, nm, s, s))
                gmap[nm] = base
            for nm in ops[i].output_arg_names():
                v = block._find_var_recursive(nm)
                if v is not None and v.persistable:
                    st = resolution.stage_for(nm)
                    if st == s:
                        opt_persist[s].add(nm)
                    elif st is None:
                        shared_persist.add(nm)
        grad_feed_param.append(gmap)

    stage_state_names = [
        sorted(set(stage_params[s]) | opt_persist[s])
        for s in range(n_stages)
    ]
    shared_state = sorted(
        shared_persist
        | {nm for per in fwd_persist for nm in per if nm not in owner})

    state_bytes = [
        sum(_var_bytes(block, nm, batch_hint) for nm in names)
        for names in stage_state_names
    ]
    act_bytes = []
    for s, (lo, hi) in enumerate(stage_ranges):
        seen = set()
        a = 0
        for op in ops[lo:hi]:
            for nm in op.output_arg_names():
                if nm and nm not in seen and _is_activation(block, nm):
                    seen.add(nm)
                    a += _var_bytes(block, nm, batch_hint)
        act_bytes.append(a)

    return PipelinePlan(
        n_stages=n_stages,
        pp_axis=pp_axis,
        dp_axis=dp_axis,
        schedule=schedule,
        n_microbatches=int(n_microbatches),
        batch_hint=batch_hint,
        fwd_end=fwd_end,
        loss_name=loss_name,
        stage_ranges=stage_ranges,
        fwd_masks=fwd_masks,
        opt_masks=opt_masks,
        stage_feed_names=stage_feed_names,
        data_feeds=data_feeds,
        boundary_in=boundary_in,
        boundary_out=boundary_out,
        stage_params=stage_params,
        stage_state_names=stage_state_names,
        shared_state=shared_state,
        grad_feed_param=grad_feed_param,
        resolution=resolution,
        state_bytes=state_bytes,
        act_bytes=act_bytes,
        last_defined=sorted(defined[-1]),
    )


def pipeline_program(program, mesh, pp_axis="pp", n_microbatches=None,
                     schedule="1f1b", batch_hint=8):
    """Stamp `program` for pipeline-parallel execution over `mesh`.

    With pp size 1 the program is returned UNTOUCHED (bit-identical
    single-program path).  Otherwise the plan is built (slicing validated),
    RNG streams are pinned (PR 12 discipline: op-position seeds survive any
    later rewrites), and ``program._pipeline`` carries {mesh, plan} for the
    executor's pp dispatch path.  `n_microbatches` defaults to the pp
    degree; the autotuner's ``n_microbatches`` knob (consult-only under
    FLAGS_program_autotune=0) feeds this argument."""
    sizes = mesh_axis_sizes(mesh)
    n_stages = int(sizes.get(pp_axis, 1))
    if n_stages == 1:
        return program
    pin_rng_streams(program)
    m = int(n_microbatches) if n_microbatches else n_stages
    if m < 1:
        raise ValueError("n_microbatches must be >= 1, got %d" % m)
    dp_axis = "dp" if "dp" in sizes else None
    plan = build_pipeline_plan(
        program, n_stages, m, schedule, pp_axis=pp_axis,
        dp_axis=dp_axis, batch_hint=batch_hint)
    program._pipeline = {"mesh": mesh, "plan": plan}
    return program


# ---------------------------------------------------------------------------
# reports: the estimator-backed numbers the bench + residency tests assert
# ---------------------------------------------------------------------------
def pipeline_activation_report(program, mb_rows=None):
    """Per-schedule peak activation residency from the remat byte model:
    GPipe stashes all M in-flight microbatches per stage, 1F1B at most
    min(M, 2(S-s)-1).  `mb_rows` is rows per microbatch (defaults to the
    plan's batch_hint)."""
    pp = getattr(program, "_pipeline", None)
    if pp is None:
        raise ValueError("program is not pipeline-stamped")
    plan = pp["plan"]
    block = program.block(0)
    rows = mb_rows if mb_rows is not None else plan.batch_hint
    S = plan.n_stages
    M = plan.n_microbatches
    out = {"n_stages": S, "n_microbatches": M, "mb_rows": rows}
    for sched in _SCHEDULES:
        per = []
        for s in range(S):
            names = plan.boundary_in[s] if s else plan.data_feeds[s]
            hop = sum(_activation_bytes(block, n, rows) for n in names)
            live = sum(
                _activation_bytes(block, n, rows)
                for n in _stage_act_names(program, plan, s))
            copies = M if sched == "gpipe" else min(M, 2 * (S - s) - 1)
            per.append(copies * (hop + live))
        out[sched] = {"per_stage": per, "peak_bytes": max(per)}
    return out


def _stage_act_names(program, plan, s):
    block = program.block(0)
    lo, hi = plan.stage_ranges[s]
    seen = []
    have = set()
    for op in block.ops[lo:hi]:
        for nm in op.output_arg_names():
            if nm and nm not in have and _is_activation(block, nm):
                have.add(nm)
                seen.append(nm)
    return seen


def pipeline_state_report(program):
    """Param+optimizer-state bytes: per-stage owned, shared (replicated),
    single-device total, and the per-device peak ratio the bench gates on
    (max stage + shared vs the whole program on one device)."""
    pp = getattr(program, "_pipeline", None)
    if pp is None:
        raise ValueError("program is not pipeline-stamped")
    plan = pp["plan"]
    block = program.block(0)
    per_stage = []
    for names in plan.stage_state_names:
        per_stage.append(
            sum(_var_bytes(block, n, plan.batch_hint) for n in names))
    shared = sum(
        _var_bytes(block, n, plan.batch_hint) for n in plan.shared_state)
    single = sum(per_stage) + shared
    peak = max(per_stage) + shared
    return {
        "per_stage_bytes": per_stage,
        "shared_bytes": shared,
        "single_device_bytes": single,
        "per_device_peak_bytes": peak,
        "peak_ratio": (float(peak) / single) if single else 0.0,
    }


# ---------------------------------------------------------------------------
# runtime: traced stage fns + packed state + the scheduled step
# ---------------------------------------------------------------------------
class PipelineRuntime:
    """One compiled pipeline step for one (program, feed signature,
    fetches).  Built by the executor's pp dispatch path on cache miss;
    holds the jitted step, the packed-state layout, and enough metadata
    to flush stage-owned state back into the scope."""

    def __init__(self, jitted, fetch_names, layouts, buffer_sharding,
                 shared_ro, shared_rw, feed_shardings, plan, mesh):
        self.jitted = jitted
        self.fetch_names = fetch_names
        self.layouts = layouts  # {dtype: [per-stage [(name, off, size, shape)]]}
        self.buffer_sharding = buffer_sharding
        self.shared_ro = shared_ro  # names
        self.shared_rw = shared_rw  # names
        self.feed_shardings = feed_shardings
        self.plan = plan
        self.mesh = mesh

    def buffer_names(self):
        return ["__pp_state_" + dt for dt in sorted(self.layouts)]

    def pack_state(self, scope):
        """Gather stage-owned persistables from the scope into the [S, L]
        per-dtype buffers, device_put sharded P(pp)."""
        out = {}
        S = self.plan.n_stages
        for dt in sorted(self.layouts):
            L = max(
                (ent[1] + ent[2] for per in self.layouts[dt] for ent in per),
                default=0)
            buf = np.zeros((S, L), dtype=dt)
            for s, per in enumerate(self.layouts[dt]):
                for name, off, size, _shape in per:
                    buf[s, off:off + size] = np.asarray(
                        scope.find_var(name), dtype=dt).reshape(-1)
            out["__pp_state_" + dt] = jax.device_put(
                buf, self.buffer_sharding)
        return out

    def unpack_state(self, buffers, scope):
        """Write stage-owned persistables from the packed buffers back to
        the scope (checkpointing / inspection path, not the hot loop)."""
        for dt in sorted(self.layouts):
            buf = np.asarray(buffers["__pp_state_" + dt])
            for s, per in enumerate(self.layouts[dt]):
                for name, off, size, shape in per:
                    scope.set(name, buf[s, off:off + size].reshape(shape))


def flush_pipeline_state(program, scope):
    """Copy stage-owned params/optimizer state from the packed pp buffers
    back into `scope` (the buffers are authoritative between flushes)."""
    entry = getattr(program, "_pipeline_runtime", None)
    if entry is None:
        return False
    entry["runtime"].unpack_state(entry["state"], scope)
    return True


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def build_pipeline_runtime(program, plan, mesh, scope, feed_arrays,
                           fetch_names):
    """Build the compiled pipeline step: per-stage traced fns, packed-state
    layouts, union-carry shapes, the schedule body, and the jit wrapper
    matching the executor's (feeds, ro_state, rw_state, rng_key) calling
    discipline."""
    S = plan.n_stages
    M = plan.n_microbatches
    pp_axis = plan.pp_axis
    sizes = mesh_axis_sizes(mesh)
    dp_axis = plan.dp_axis if plan.dp_axis in sizes else None
    dp = int(sizes.get(dp_axis, 1)) if dp_axis else 1
    repl = NamedSharding(mesh, P())

    # --- batch geometry ----------------------------------------------------
    data_names = sorted({n for per in plan.data_feeds for n in per})
    missing = [n for n in data_names if n not in feed_arrays]
    if missing:
        raise ValueError("pipeline program needs feeds %s" % missing)
    lead = {feed_arrays[n].shape[0] for n in data_names}
    if len(lead) != 1:
        raise ValueError(
            "pipeline data feeds disagree on batch dim: %s"
            % {n: feed_arrays[n].shape for n in data_names})
    b_global = lead.pop()
    if b_global % (dp * M) != 0:
        raise ValueError(
            "global batch %d must divide by dp*n_microbatches = %d*%d"
            % (b_global, dp, M))
    b_local = b_global // dp
    mb = b_local // M

    # --- traced stage forward + optimizer fns ------------------------------
    internal_fetch = [plan.loss_name] + [
        n for n in fetch_names if n != plan.loss_name]
    last_ok = set(plan.last_defined) | set(plan.stage_feed_names[-1])
    bad = [n for n in internal_fetch if n not in last_ok]
    if bad:
        raise NotImplementedError(
            "fetch targets %s are not produced by the last pipeline stage "
            "— only last-stage scalars (loss, counters) can be fetched "
            "under pipelining" % bad)

    stage_fetch = [list(plan.boundary_out[s]) for s in range(S - 1)]
    stage_fetch.append(internal_fetch)
    traced_fwd = []
    for s in range(S):
        t = build_traced_function(
            program, 0, plan.stage_feed_names[s], stage_fetch[s], scope,
            keep=plan.fwd_masks[s])
        if t.rw_names or t.updated:
            raise NotImplementedError(
                "pipeline stage %d forward writes persistable state %s "
                "(e.g. BN statistics) — not supported" % (s, t.updated))
        traced_fwd.append(t)

    grad_names = [sorted(plan.grad_feed_param[s]) for s in range(S)]
    traced_opt = [
        build_traced_function(
            program, 0, grad_names[s], (), scope, keep=plan.opt_masks[s])
        for s in range(S)
    ]
    shared_rw = sorted({
        n for t in traced_opt for n in t.updated if n in set(plan.shared_state)
    })
    shared_ro = sorted(
        {n
         for t in traced_fwd + traced_opt
         for n in t.ro_names
         if n in set(plan.shared_state)} - set(shared_rw))

    # --- packed state layouts ---------------------------------------------
    owned_vals = []
    for s in range(S):
        vals = {}
        for n in plan.stage_state_names[s]:
            vals[n] = np.asarray(scope.find_var(n))
        owned_vals.append(vals)
    dtypes = sorted({str(v.dtype) for vals in owned_vals for v in
                     vals.values()})
    layouts = {dt: [] for dt in dtypes}
    for dt in dtypes:
        for s in range(S):
            per = []
            off = 0
            for n in plan.stage_state_names[s]:
                v = owned_vals[s][n]
                if str(v.dtype) != dt:
                    continue
                per.append((n, off, int(v.size), tuple(v.shape)))
                off += int(v.size)
            layouts[dt].append(per)
    buffer_sharding = NamedSharding(mesh, P(pp_axis))
    stage_of_name = {}
    for s in range(S):
        for n in plan.stage_state_names[s]:
            stage_of_name[n] = s

    def unflatten(s, rows):
        out = {}
        for dt in dtypes:
            for name, off, size, shape in layouts[dt][s]:
                out[name] = rows[dt][off:off + size].reshape(shape)
        return out

    def reflatten(s, rows, updates):
        new = dict(rows)
        for dt in dtypes:
            r = new[dt]
            for name, off, size, shape in layouts[dt][s]:
                if name in updates:
                    r = r.at[off:off + size].set(
                        jnp.asarray(updates[name], r.dtype).reshape(-1))
            new[dt] = r
        return new

    # --- abstract union-carry shapes via eval_shape chain ------------------
    key_abs = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    data_abs = {
        n: jax.ShapeDtypeStruct((mb,) + feed_arrays[n].shape[1:],
                                feed_arrays[n].dtype)
        for n in data_names
    }
    union_specs = {}
    fetch_specs = {}
    for s in range(S):
        feeds_abs = {}
        for n in plan.stage_feed_names[s]:
            feeds_abs[n] = data_abs[n] if n in data_abs else union_specs[n]
        ro_abs = {}
        for n in traced_fwd[s].ro_names:
            v = (owned_vals[s].get(n)
                 if n in owned_vals[s] else scope.find_var(n))
            v = np.asarray(v)
            ro_abs[n] = jax.ShapeDtypeStruct(v.shape, v.dtype)
        fetches_abs, _ = jax.eval_shape(
            traced_fwd[s].fn, feeds_abs, ro_abs, {}, key_abs)
        for n, a in zip(stage_fetch[s], fetches_abs):
            spec = jax.ShapeDtypeStruct(a.shape, a.dtype)
            if s < S - 1:
                union_specs[n] = spec
            else:
                fetch_specs[n] = spec
    union_names = sorted(union_specs)
    for n in union_names:
        if not jnp.issubdtype(union_specs[n].dtype, jnp.inexact):
            raise NotImplementedError(
                "stage boundary value %r has non-float dtype %s — the "
                "backward hop cannot carry its cotangent" %
                (n, union_specs[n].dtype))
    for n, spec in fetch_specs.items():
        if int(np.prod(spec.shape)) != 1:
            raise NotImplementedError(
                "fetch %r has shape %s — pipeline fetches must be scalars "
                "(losses, counters); fetch activations from an unpipelined "
                "clone instead" % (n, spec.shape))

    data_set = set(data_names)
    shared_ro_set = set(shared_ro)
    norm = float(M * dp)

    # --- per-stage switch branches ----------------------------------------
    def make_fwd_branch(s):
        def branch(rows, union, feeds_mb, sro, srw, key, row_offset):
            f = {}
            for n in plan.stage_feed_names[s]:
                f[n] = feeds_mb[n] if n in data_set else union[n]
            state = unflatten(s, rows)

            def look(n):
                if n in state:
                    return state[n]
                if n in shared_ro_set:
                    return sro[n]
                return srw[n]

            ro = {n: look(n) for n in traced_fwd[s].ro_names}
            with microbatch_rows(b_global, row_offset):
                fetches, _ = traced_fwd[s].fn(f, ro, {}, key)
            new_union = dict(union)
            if s < S - 1:
                for n, v in zip(stage_fetch[s], fetches):
                    new_union[n] = v
                loss = jnp.zeros((), jnp.float32)
                fvals = {n: jnp.zeros(fetch_specs[n].shape,
                                      fetch_specs[n].dtype)
                         for n in internal_fetch}
            else:
                got = dict(zip(stage_fetch[s], fetches))
                loss = _f32(got[plan.loss_name]).reshape(())
                fvals = {n: jnp.asarray(got[n], fetch_specs[n].dtype)
                         for n in internal_fetch}
            return new_union, loss, fvals

        return branch

    fwd_branches = [make_fwd_branch(s) for s in range(S)]

    def make_opt_branch(s):
        def branch(rows, grows, sro, srw, key):
            state = unflatten(s, rows)
            gfull = unflatten(s, grows)
            gfeeds = {g: jnp.asarray(gfull[p], state[p].dtype)
                      for g, p in plan.grad_feed_param[s].items()}
            ro = {}
            for n in traced_opt[s].ro_names:
                ro[n] = state[n] if n in state else (
                    sro[n] if n in shared_ro_set else srw[n])
            rw = {}
            for n in traced_opt[s].rw_names:
                rw[n] = state[n] if n in state else srw[n]
            _, new_state = traced_opt[s].fn(gfeeds, ro, rw, key)
            owned_new = {n: v for n, v in new_state.items()
                         if stage_of_name.get(n) == s}
            new_rows = reflatten(s, rows, owned_new)
            new_shared = {
                n: jnp.asarray(new_state.get(n, srw[n]),
                               jnp.asarray(srw[n]).dtype).reshape(
                                   jnp.asarray(srw[n]).shape)
                for n in shared_rw
            }
            return new_rows, new_shared

        return branch

    opt_branches = [make_opt_branch(s) for s in range(S)]

    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [((i + 1) % S, i) for i in range(S)]

    def union_zero():
        return {
            n: pcast_varying(
                jnp.zeros(union_specs[n].shape, union_specs[n].dtype),
                (pp_axis,))
            for n in union_names
        }

    def fetch_zero():
        return {n: jnp.zeros(fetch_specs[n].shape, fetch_specs[n].dtype)
                for n in internal_fetch}

    def psum_all(x):
        x = jax.lax.psum(x, pp_axis)
        if dp_axis:
            x = jax.lax.psum(x, dp_axis)
        return x

    def device_step(feeds_local, sro, rw_local, key):
        s_idx = jax.lax.axis_index(pp_axis)
        dp_idx = jax.lax.axis_index(dp_axis) if dp_axis else 0
        rows = {dt: rw_local["__pp_state_" + dt][0] for dt in dtypes}
        srw = {n: rw_local[n] for n in shared_rw}
        feeds_resh = {
            n: feeds_local[n].reshape((M, mb) + feeds_local[n].shape[1:])
            for n in data_names
        }

        def feeds_at(m):
            return {
                n: jax.lax.dynamic_index_in_dim(a, m, 0, keepdims=False)
                for n, a in feeds_resh.items()
            }

        def run_stage(rows_, union, m):
            row_offset = dp_idx * b_local + m * mb
            return jax.lax.switch(
                s_idx, fwd_branches, rows_, union, feeds_at(m), sro, srw,
                key, row_offset)

        is_last = s_idx == S - 1

        if plan.schedule == "gpipe":
            def sched_loss(rows_):
                def tick(carry, t):
                    union, loss_acc, facc = carry
                    m_f = t - s_idx
                    m = jnp.clip(m_f, 0, M - 1)
                    new_union, loss_mb, fvals = run_stage(rows_, union, m)
                    emit = is_last & (m_f >= 0) & (m_f < M)
                    loss_acc = loss_acc + jnp.where(emit, loss_mb, 0.0)
                    facc = {
                        n: facc[n] + jnp.where(emit, _f32(fvals[n]),
                                               0.0).reshape(facc[n].shape)
                        for n in internal_fetch
                    }
                    sent = jax.tree_util.tree_map(
                        lambda v: jax.lax.ppermute(v, pp_axis, fwd_perm),
                        new_union)
                    return (sent, loss_acc, facc), None

                facc0 = {n: jnp.zeros((), jnp.float32)
                         for n in internal_fetch}
                init = (union_zero(), jnp.zeros((), jnp.float32), facc0)
                (_, loss_acc, facc), _ = jax.lax.scan(
                    tick, init, jnp.arange(M + S - 1))
                total = psum_all(loss_acc) / norm
                return total, facc

            (loss, facc), grows = jax.value_and_grad(
                sched_loss, has_aux=True)(rows)
        else:  # 1f1b
            buf_n = 2 * S - 1

            def tick(carry, t):
                union_f, ct_b, stash, loss_acc, facc, gacc = carry
                m_f = t - s_idx
                do_f = (m_f >= 0) & (m_f < M)
                mf = jnp.clip(m_f, 0, M - 1)
                m_b = t - (2 * S - 1) + s_idx
                do_b = (m_b >= 0) & (m_b < M)
                mbi = jnp.clip(m_b, 0, M - 1)
                slot_f = jnp.mod(mf, buf_n)
                slot_b = jnp.mod(mbi, buf_n)

                # read the stashed backward input BEFORE the forward
                # stash write lands in the same circular buffer
                x_res = jax.tree_util.tree_map(
                    lambda b: jax.lax.dynamic_index_in_dim(
                        b, slot_b, 0, keepdims=False), stash)

                new_union, loss_mb, fvals = run_stage(rows, union_f, mf)
                emit_f = is_last & do_f
                loss_acc = loss_acc + jnp.where(emit_f, loss_mb, 0.0)
                facc = {
                    n: facc[n] + jnp.where(emit_f, _f32(fvals[n]),
                                           0.0).reshape(facc[n].shape)
                    for n in internal_fetch
                }
                stash = jax.tree_util.tree_map(
                    lambda b, v: b.at[slot_f].set(
                        jnp.where(do_f, v, b[slot_f])),
                    stash, union_f)

                def fwd_for_vjp(rows_, union_in):
                    nu, lm, _ = run_stage(rows_, union_in, mbi)
                    return {n: nu[n] for n in union_names}, lm

                _, pull = jax.vjp(fwd_for_vjp, rows, x_res)
                ct_u = {
                    n: jnp.where(is_last, jnp.zeros_like(ct_b[n]), ct_b[n])
                    for n in union_names
                }
                ct_loss = jnp.where(
                    is_last & do_b, jnp.float32(1.0) / norm, 0.0)
                dr, du = pull((ct_u, ct_loss))
                gacc = jax.tree_util.tree_map(
                    lambda a, d: a + jnp.where(do_b, d, 0.0), gacc, dr)
                bwd_send = jax.tree_util.tree_map(
                    lambda d: jax.lax.ppermute(
                        jnp.where(do_b, d, 0.0), pp_axis, bwd_perm), du)
                fwd_send = jax.tree_util.tree_map(
                    lambda v: jax.lax.ppermute(v, pp_axis, fwd_perm),
                    new_union)
                return (fwd_send, bwd_send, stash, loss_acc, facc,
                        gacc), None

            stash0 = {
                n: jnp.zeros((buf_n,) + union_specs[n].shape,
                             union_specs[n].dtype)
                for n in union_names
            }
            gacc0 = {dt: jnp.zeros_like(rows[dt]) for dt in dtypes}
            facc0 = {n: jnp.zeros((), jnp.float32) for n in internal_fetch}
            init = (union_zero(), union_zero(), stash0,
                    jnp.zeros((), jnp.float32), facc0, gacc0)
            (_, _, _, loss_acc, facc, grows), _ = jax.lax.scan(
                tick, init, jnp.arange(M + 2 * S - 1))
            loss = psum_all(loss_acc) / norm

        if dp_axis:
            grows = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, dp_axis), grows)

        new_rows, new_shared = jax.lax.switch(
            s_idx, opt_branches, rows, grows, sro, srw, key)

        fetch_out = {}
        for n in internal_fetch:
            if n == plan.loss_name:
                fetch_out[n] = jnp.asarray(loss, fetch_specs[n].dtype
                                           ).reshape(fetch_specs[n].shape)
            else:
                v = psum_all(facc[n])
                fetch_out[n] = jnp.asarray(v, fetch_specs[n].dtype
                                           ).reshape(fetch_specs[n].shape)
        new_state = {"__pp_state_" + dt: new_rows[dt][None] for dt in dtypes}
        new_state.update(new_shared)
        return fetch_out, new_state

    # --- shard_map + jit wrapper ------------------------------------------
    def feed_spec(n):
        a = feed_arrays[n]
        if dp_axis and dp > 1 and a.ndim >= 1:
            return P(*((dp_axis,) + (None,) * (a.ndim - 1)))
        return P()

    feed_specs = {n: feed_spec(n) for n in data_names}
    rw_specs = {"__pp_state_" + dt: P(pp_axis) for dt in dtypes}
    rw_specs.update({n: P() for n in shared_rw})
    ro_specs = {n: P() for n in shared_ro}
    out_specs = ({n: P() for n in internal_fetch},
                 dict(rw_specs))

    def step_fn(feeds, ro_state, rw_state, rng_key):
        fetch_out, new_state = shard_map(
            device_step, mesh=mesh,
            in_specs=(feed_specs, ro_specs, dict(rw_specs), P()),
            out_specs=out_specs,
            check_vma=False,
        )(feeds, ro_state, rw_state, rng_key)
        return [fetch_out[n] for n in fetch_names], new_state

    feed_shardings = {n: NamedSharding(mesh, feed_specs[n])
                      for n in data_names}
    rw_shardings = {"__pp_state_" + dt: buffer_sharding for dt in dtypes}
    rw_shardings.update({n: repl for n in shared_rw})
    jitted = jax.jit(
        step_fn,
        in_shardings=(
            {n: feed_shardings[n] for n in data_names},
            {n: repl for n in shared_ro},
            rw_shardings,
            repl,
        ),
        out_shardings=(None, rw_shardings),
        donate_argnums=(2,),
    )
    return PipelineRuntime(
        jitted, list(fetch_names), layouts, buffer_sharding,
        shared_ro, shared_rw, feed_shardings, plan, mesh)
