"""Peak-activation-memory estimation over traced programs.

The PR 11 "logits never materialize" test walked every aval of a traced
fwd+bwd jaxpr to prove a buffer ABSENT; this module generalizes that
machinery into an analysis tool: a liveness walk over the jaxpr's
equations that estimates the peak number of simultaneously-live
intermediate bytes — the quantity an HBM budget constrains and the
rematerialization pass (transpiler.remat) optimizes.

Two deliberate properties:

* **Remat-aware.**  Call-like equations (``remat2``/``checkpoint``,
  ``pjit``, ``custom_vjp_call``, ``scan``...) recurse: a sub-jaxpr's
  internal buffers contribute a TRANSIENT spike at that equation, not
  live ranges in the outer frame.  ``jax.checkpoint`` regions therefore
  show exactly the memory the trade buys: their internals stop being
  long-lived residuals and become per-call working set.
* **Activations only.**  The top-level invars (parameters, optimizer
  state, feeds) and constants are excluded — they are resident
  regardless of scheduling; the estimator prices what the SCHEDULE
  controls.

This is an estimate, not an XLA allocator replay: fusion can elide
buffers and donation can alias them.  It is monotone under
checkpointing and ranks programs correctly, which is what budgeted
remat and the program autotuner need (docs/PERFORMANCE.md
"Optimization transpiler layer").
"""

import numpy as np

__all__ = [
    "jaxpr_peak_bytes",
    "trace_fwd_bwd",
    "estimate_peak_activation_bytes",
    "program_feed_specs",
]


def _aval_bytes(aval):
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    try:
        return n * np.dtype(dtype).itemsize
    except TypeError:
        # extended dtypes (PRNG keys): key avals carry an itemsize-less
        # dtype; 4 bytes/elem is the right order for the uint32 pairs
        return n * 4


def _sub_jaxprs(val):
    """Yield any Jaxpr / ClosedJaxpr reachable from an eqn param value."""
    import jax.extend.core as jcore

    vals = val if isinstance(val, (list, tuple)) else [val]
    for v in vals:
        if isinstance(v, jcore.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jcore.Jaxpr):
            yield v


def jaxpr_peak_bytes(jaxpr, stream_outvars=True):
    """Liveness walk over one jaxpr: returns (peak_bytes, largest_buf).

    Live set = values defined by earlier eqns whose last textual use is
    at or after the current eqn.  invars/constvars are excluded (see
    module docstring), and with ``stream_outvars`` (the top-level
    default) the jaxpr's RESULTS are excluded too: a training trace
    returns the parameter gradients, which stream into the optimizer
    apply and are byte-identical across every remat candidate — at
    transformer-base scale they are ~240 MB that would otherwise swamp
    the ~tens-of-MB activation signal this estimator exists to rank.
    Sub-jaxprs recurse with stream_outvars=False (a call's results must
    exist when it returns).  A call-like eqn adds its sub-jaxpr's own
    peak as a transient on top of the bytes live across it."""
    import jax.extend.core as jcore

    jaxpr = jaxpr.jaxpr if isinstance(jaxpr, jcore.ClosedJaxpr) else jaxpr
    eqns = jaxpr.eqns
    excluded = set(map(id, list(jaxpr.invars) + list(jaxpr.constvars)))
    if stream_outvars:
        excluded.update(map(id, [v for v in jaxpr.outvars
                                 if isinstance(v, jcore.Var)]))

    last_use = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if isinstance(v, jcore.Var):
                last_use[id(v)] = i
    if not stream_outvars:
        for v in jaxpr.outvars:
            if isinstance(v, jcore.Var):
                last_use[id(v)] = len(eqns)

    live = {}  # id(var) -> bytes
    peak = 0
    largest = 0
    for i, eqn in enumerate(eqns):
        inner_peak = 0
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                sp, sl = jaxpr_peak_bytes(sub, stream_outvars=False)
                inner_peak = max(inner_peak, sp)
                largest = max(largest, sl)
        for v in eqn.outvars:
            if isinstance(v, jcore.Var) and id(v) not in excluded:
                if last_use.get(id(v), -1) >= i:
                    b = _aval_bytes(v.aval)
                    live[id(v)] = b
                    largest = max(largest, b)
        peak = max(peak, sum(live.values()) + inner_peak)
        # free values whose last use is this eqn
        for v in eqn.invars:
            if isinstance(v, jcore.Var) and last_use.get(id(v)) == i:
                live.pop(id(v), None)
        for v in eqn.outvars:
            if isinstance(v, jcore.Var) and last_use.get(id(v), -1) <= i:
                live.pop(id(v), None)
    return peak, largest


class _SpecScope:
    """Scope stand-in for shape-level tracing: ``build_traced_function``
    insists every non-fed read exists in the scope; at program-BUILD time
    (before any startup run) only the var metadata exists.  This scope
    answers has_var from the program's var table, so the trace can run on
    ShapeDtypeStructs synthesized from the declared shapes."""

    def __init__(self, program):
        self._block = program.global_block()

    def has_var(self, name):
        return self._block._find_var_recursive(name) is not None

    def find_var(self, name):  # pragma: no cover - lowerings never peek
        return None


def program_feed_specs(program, feed_names, batch_hint=8):
    """(name -> (shape, dtype)) for the program's feed vars, resolving
    the dynamic batch dim (-1) to `batch_hint`."""
    block = program.global_block()
    specs = {}
    for name in feed_names:
        v = block._find_var_recursive(name)
        if v is None or v.shape is None:
            raise ValueError(
                "feed var %r has no declared shape; pass explicit "
                "feed_specs" % name)
        shape = tuple(batch_hint if int(d) < 0 else int(d)
                      for d in v.shape)
        specs[name] = (shape, v.dtype or "float32")
    return specs


def trace_fwd_bwd(program, feed_specs, loss_name, scope=None,
                  wrt="params"):
    """Trace the program's forward + backward into ONE ClosedJaxpr.

    The program is traced shape-level (no scope values needed): feeds
    and state become ShapeDtypeStructs from the declared var metadata,
    and ``jax.grad`` of the (summed) loss w.r.t. the trainable float
    parameters appends the backward.  Works on programs BEFORE
    ``minimize`` — which is exactly when the remat pass runs — and on
    post-minimize programs (whose explicit grad ops then simply trace
    as more forward ops).

    wrt="params" differentiates w.r.t. trainable Parameters; "none"
    traces the forward only."""
    import jax
    import jax.numpy as jnp

    from ..core.trace import build_traced_function
    from ..framework import Parameter

    spec_scope = _SpecScope(program) if scope is None else scope
    feed_names = tuple(sorted(feed_specs))
    traced = build_traced_function(
        program, 0, feed_names, [loss_name], spec_scope)
    block = program.global_block()

    feeds = {
        n: jax.ShapeDtypeStruct(tuple(shape), np.dtype(str(dtype)))
        for n, (shape, dtype) in feed_specs.items()
    }

    def struct_of(n):
        v = block._find_var_recursive(n)
        if scope is not None and hasattr(scope, "find_var"):
            arr = scope.find_var(n)
            if arr is not None and hasattr(arr, "shape"):
                return jax.ShapeDtypeStruct(
                    tuple(arr.shape), np.dtype(str(arr.dtype)))
        if v is None or v.shape is None or any(
                int(d) < 0 for d in v.shape):
            raise ValueError(
                "state var %r lacks static shape metadata" % n)
        dt = v.dtype or "float32"
        return jax.ShapeDtypeStruct(
            tuple(int(d) for d in v.shape),
            jnp.bfloat16 if dt == "bfloat16" else np.dtype(str(dt)))

    ro = {n: struct_of(n) for n in traced.ro_names}
    rw = {n: struct_of(n) for n in traced.rw_names}

    def is_trainable(n):
        v = block._find_var_recursive(n)
        return (isinstance(v, Parameter) and getattr(v, "trainable", True)
                and str(v.dtype) in ("float32", "float64", "bfloat16",
                                     "float16"))

    diff_names = (sorted(n for n in list(ro) + list(rw) if is_trainable(n))
                  if wrt == "params" else [])
    key = jax.random.PRNGKey(0)

    def fwd(diff, feeds, ro, rw, key):
        ro2 = {n: diff.get(n, v) for n, v in ro.items()}
        rw2 = {n: diff.get(n, v) for n, v in rw.items()}
        fetches, _state = traced.fn(feeds, ro2, rw2, key)
        return jnp.sum(fetches[0].astype(jnp.float32))

    if diff_names:
        def fn(feeds, ro, rw, key):
            diff = {n: (ro[n] if n in ro else rw[n]) for n in diff_names}
            loss, grads = jax.value_and_grad(fwd)(diff, feeds, ro, rw, key)
            return loss, grads
    else:
        def fn(feeds, ro, rw, key):
            return fwd({}, feeds, ro, rw, key)

    return jax.make_jaxpr(fn)(feeds, ro, rw, key)


def estimate_peak_activation_bytes(program, feed_specs, loss_name,
                                   scope=None, wrt="params"):
    """The one entry point: {'peak_bytes', 'largest_buffer_bytes',
    'n_eqns'} for the traced fwd(+bwd) of `program`.

    feed_specs: {name: (shape, dtype)} — use ``program_feed_specs`` to
    derive it from the program's data vars with a batch hint."""
    closed = trace_fwd_bwd(program, feed_specs, loss_name, scope=scope,
                           wrt=wrt)
    peak, largest = jaxpr_peak_bytes(closed)
    return {
        "peak_bytes": int(peak),
        "largest_buffer_bytes": int(largest),
        "n_eqns": len(closed.jaxpr.eqns),
    }
