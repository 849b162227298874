"""Per-(kernel, shape-bucket) block-size tuning cache for the Pallas
kernel layer (the seed of the TVM-style autotuner, ROADMAP item 2).

Every `pallas_call` site in ops/pallas_kernels.py picks its block sizes
through `tuned_params`: the discrete knob space (block_q/block_k,
block_rows, matmul tiles...) is a *searched, cached* decision instead of
a hand-pick.  Keys are (kernel, shape bucket, dtype, device kind);
values are the winning params plus provenance (searched vs seeded) and
the measured search cost.  The cache persists as JSON at
FLAGS_kernel_tune_cache, so a fleet warms once per shape bucket and
every later process (or CI, with a pinned cache and
FLAGS_kernel_autotune=0) dispatches without ever searching.

Search happens at FIRST REAL-DEVICE DISPATCH: lowering runs under a jax
trace, so candidates are timed on synthetic operands of the call-site
shapes through a standalone jit of the kernel (compile-time work — the
model step itself is never perturbed).  In interpret mode (CPU tests)
timings are meaningless, so misses seed the heuristic default and are
counted, never searched.

Attribution counters (`note_kernel` / `attribution()`): per-family
pallas-hit counts and tuning hit/miss/search totals (chip_smoke.py
reads them), so an MFU regression can be pinned to "kernel X stopped dispatching" or
"cache went cold" instead of guessed at.  Counts tick at TRACE time
(once per compiled program, not per step) — they attribute what the
compiled step contains, not how often it runs.
"""

import threading
import time

__all__ = [
    "tuned_params",
    "shape_bucket",
    "note_kernel",
    "note_dense_vjp",
    "attribution",
    "reset_attribution",
    "measure_candidate",
    "cache_stats",
    "clear_cache",
]

_lock = threading.RLock()
_cache = None  # key -> {"params": {...}, "searched": bool, "search_ms": float}
_cache_path = None  # path the in-memory cache was loaded from
_STATS_ZERO = {"hits": 0, "misses": 0, "searches": 0, "search_ms": 0.0,
               "failed_candidates": 0, "last_failure": ""}
_stats = dict(_STATS_ZERO)
_kernel_hits = {}  # family -> pallas dispatch count (trace-time)
_dense_vjp_hits = {}  # family -> hand-written plain-XLA VJP engagements
_rng_draws = {}  # generator ("rbg" / "threefry") -> draw sites traced
_uneven_constraints = {}  # op type -> uneven weight constraints placed
# moe_ffn lowerings that ran a share's row work over the live chunks
_moe_live_chunks = {"ops": 0, "chunk_rows": {}}  # chunk_rows: N*k -> rows a chunk
# windowed fused_attention lowerings that took the flash kernel, with the
# forward grid steps a head walks and the tiles its band computes
_attention_band_grid = {"ops": 0, "steps": {}}  # "TxWxBQxBK" -> [walked, computed]
_searching = threading.local()  # candidate timing in flight on this thread
_inflight = {}  # key -> threading.Event: a measured search under way


def _flag(name):
    from ..flags import get_flag

    return get_flag(name)


def _device_kind():
    """Stable device identity for cache keys; interpret-mode (CPU) runs
    are their own universe so a CI cache never leaks onto a real chip."""
    import jax

    try:
        d = jax.devices()[0]
    except RuntimeError:
        return "unknown"
    if d.platform != "tpu":
        return "interpret-%s" % d.platform
    return (getattr(d, "device_kind", "") or d.platform).replace(" ", "_")


def _pow2_bucket(n):
    n = int(n)
    if n <= 1:
        return 1
    p = 1
    while p < n:
        p *= 2
    return p


def shape_bucket(shapes):
    """Canonical bucket string: leading (row/batch) dims round up to the
    next power of two — one searched entry serves every batch in the
    bucket — while the last (feature/lane) dim of each operand stays
    exact, since it decides Mosaic legality and VMEM footprint."""
    parts = []
    for shape in shapes:
        dims = [int(d) for d in shape]
        if len(dims) <= 1:
            parts.append("x".join(str(d) for d in dims))
        else:
            parts.append("x".join(
                [str(_pow2_bucket(d)) for d in dims[:-1]]
                + [str(dims[-1])]))
    return ",".join(parts)


def _key(kernel, shapes, dtype):
    return "|".join([kernel, shape_bucket(shapes), str(dtype),
                     _device_kind()])


def _entry_valid(v):
    return isinstance(v.get("params"), dict)


def _load_locked():
    global _cache, _cache_path
    from ..utils.tune_cache import load_entries

    path = str(_flag("kernel_tune_cache") or "")
    if _cache is not None and path == _cache_path:
        return
    _cache_path = path
    _cache = load_entries(path, _entry_valid, "kernel tuning cache")


def _save_locked():
    # searched decisions only, merged with concurrent writers' searched
    # entries, atomic replace — the shared utils.tune_cache discipline
    # (a seeded default stays process-local; a pinned CI cache never
    # gains entries)
    from ..utils.tune_cache import save_entries

    save_entries(_cache_path, _cache, _entry_valid,
                 "kernel tuning cache")


def _search_allowed(measure):
    """Measured search only when explicitly injected (tests) or running
    on a real accelerator with FLAGS_kernel_autotune on."""
    if not _flag("kernel_autotune"):
        return False
    if measure is not None:
        return True
    from .pallas_kernels import _interpret

    return not _interpret()


def measure_candidate(build_fn, arg_specs, warmup=1, iters=3, seed=0):
    """Default measurer: time `build_fn(params)` — a callable over
    positional arrays — on synthetic operands of `arg_specs`
    [(shape, dtype), ...].  Returns median seconds/call (compiled,
    block_until_ready).  Raises whatever the candidate raises, so the
    caller can skip illegal block configurations.  Operands materialize
    LAZILY at the first timing call: a measurer is constructed on every
    real-device consult, almost all of which are cache hits that never
    measure — building full-size device arrays up front would burn HBM
    and transfer time for nothing."""
    import jax

    state = {}

    def _args():
        import numpy as np

        if "args" not in state:
            rng = np.random.RandomState(seed)
            args = []
            for shape, dtype in arg_specs:
                if str(dtype).startswith("int"):
                    args.append(jax.numpy.asarray(
                        rng.randint(0, 2, size=shape), dtype=dtype))
                else:
                    args.append(jax.numpy.asarray(
                        rng.randn(*shape) * 0.1, dtype=dtype))
            state["args"] = args
        return state["args"]

    def run(fn):
        out = fn(*_args())
        jax.block_until_ready(out)
        return out

    def bench(params):
        fn = jax.jit(build_fn(params))
        for _ in range(warmup):
            run(fn)
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            run(fn)
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]

    return bench


def tuned_params(kernel, shapes, dtype, candidates, default, measure=None):
    """The one entry point: returns the block-size params dict for this
    (kernel, shapes, dtype) call site.

    candidates: list of param dicts (the discrete search space; may be
    empty).  default: the heuristic params used when no search runs.
    measure: optional params -> seconds callable (injected by tests and
    by real-device call sites via `measure_candidate`); a candidate that
    raises is skipped and counted (illegal block shapes surface as
    compile errors; `attribution()["tuning"]` carries failed_candidates
    and the last message), and a search in which EVERY candidate raised
    raises RuntimeError with that message.

    Cache hit -> cached params.  Miss -> search when allowed (real
    device or injected measure, FLAGS_kernel_autotune on), else seed the
    default; either way the decision is recorded (and persisted when
    FLAGS_kernel_tune_cache names a file) so it is made once per shape
    bucket per device kind."""
    with _lock:
        _load_locked()
        key = _key(kernel, shapes, dtype)
        hit = _cache.get(key)
        if hit is not None:
            _stats["hits"] += 1
            return dict(hit["params"])
        if not (candidates and _search_allowed(measure)):
            _stats["misses"] += 1
            entry = {"params": dict(default), "searched": False,
                     "search_ms": 0.0}
            _cache[key] = entry
            return dict(entry["params"])
        waiter = _inflight.get(key)
        if waiter is None:
            _inflight[key] = threading.Event()
            _stats["misses"] += 1

    if waiter is not None:
        # another thread is measuring this key: wait for its decision
        # instead of racing a duplicate search (the timeout is a hedge
        # against a searcher dying without its finally — fall back to
        # the heuristic default rather than hang the trace)
        waiter.wait(timeout=600.0)
        with _lock:
            hit = _cache.get(key)
            if hit is not None:
                _stats["hits"] += 1
                return dict(hit["params"])
        return dict(default)

    # measure OUTSIDE the lock: a search is compile + warmup + timed
    # runs per candidate (seconds to minutes on a real chip) and must
    # not serialize other threads' consults — cache hits for unrelated
    # kernels keep flowing while this key searches
    entry = None
    ms = 0.0
    failures = []
    try:
        t0 = time.perf_counter()
        best, best_t = None, None
        # candidate compiles re-trace the kernel bodies: mute the
        # per-family hit counters meanwhile, or one searched miss
        # with N candidates would report N phantom dispatches and
        # corrupt the bench attribution
        _searching.active = True
        try:
            for cand in candidates:
                try:
                    t = measure(dict(cand))
                except Exception as e:  # illegal blocks: count, search on
                    failures.append("%s: %s" % (cand, str(e)[:400]))
                    continue
                if best_t is None or t < best_t:
                    best, best_t = dict(cand), t
        finally:
            _searching.active = False
        ms = (time.perf_counter() - t0) * 1e3
        if best is not None:
            entry = {"params": best, "searched": True,
                     "search_ms": round(ms, 3)}
    finally:
        with _lock:
            _stats["failed_candidates"] += len(failures)
            if failures:
                _stats["last_failure"] = failures[-1]
            if entry is not None:
                _cache[key] = entry
                _stats["searches"] += 1
                _stats["search_ms"] += ms
                # only measured decisions persist: seeded defaults are
                # deterministic heuristics (nothing to remember), and a
                # CI run against a pinned read-only cache must not
                # dirty it
                _save_locked()
            ev = _inflight.pop(key, None)
            if ev is not None:
                ev.set()
    if entry is None:
        # every candidate failed: the default is built from the same
        # kernel, so seeding it silently would only move the compiler's
        # refusal to the model's own trace with the reason lost
        raise RuntimeError(
            "kernel tuning: all %d candidates for %s failed; last: %s"
            % (len(failures), key, failures[-1]))
    return dict(entry["params"])


def note_kernel(family, n=1):
    """Count a pallas dispatch for `family` (attention / matmul-epilogue
    / xent / layernorm).  Trace-time counter; muted while a
    block-size search times candidates (those traces are not program
    content)."""
    if getattr(_searching, "active", False):
        return
    with _lock:
        _kernel_hits[family] = _kernel_hits.get(family, 0) + n


def note_dense_vjp(family):
    """Count a trace-time engagement of a hand-written VJP in plain XLA
    ops (no Mosaic call: not a pallas hit) for `family`."""
    with _lock:
        _dense_vjp_hits[family] = _dense_vjp_hits.get(family, 0) + 1


def note_rng_draw(impl):
    """Count a trace-time request of a key by a randomness-consuming op
    (LowerCtx.rng), by the generator the key draws from."""
    with _lock:
        _rng_draws[impl] = _rng_draws.get(impl, 0) + 1


def note_uneven_constraint(op_type):
    """Count a trace-time sharding constraint that a lowering placed on a
    weight stored replicated but computed in uneven shards
    (PartitionRules.compute_spec_for), by the op type that placed it."""
    with _lock:
        _uneven_constraints[op_type] = _uneven_constraints.get(op_type, 0) + 1


def note_live_chunks(rows, chunk_rows):
    """Count a trace-time lowering of a `moe_ffn` that holds a share of
    its experts, whose row work runs over the live chunks of its `rows`
    (N k) row buffers, and keep the rows of a chunk it chose."""
    with _lock:
        _moe_live_chunks["ops"] += 1
        _moe_live_chunks["chunk_rows"][int(rows)] = int(chunk_rows)


def note_band_grid(t, window, block_q, block_k, walked, computed):
    """Count a trace-time lowering of a windowed `fused_attention` to the
    flash kernel, and keep by shape the grid steps a head's forward walks
    and the tiles its band lets compute."""
    with _lock:
        _attention_band_grid["ops"] += 1
        _attention_band_grid["steps"]["%dx%dx%dx%d" % (
            t, window, block_q, block_k)] = [int(walked), int(computed)]


def attribution():
    """Snapshot for bench attribution: per-family pallas-hit counts,
    in-program random draws by generator, uneven weight constraints by op
    type, the moe_ffn lowerings that took the live-chunk path with the
    rows of a chunk by buffer size, the windowed flash lowerings with their
    forward grid steps walked and computed by shape, plus tuning-cache
    hit/miss/search totals (search_ms summed)."""
    with _lock:
        return {
            "pallas_hits": dict(_kernel_hits),
            "dense_vjp_hits": dict(_dense_vjp_hits),
            "rng_draws": {"rbg": 0, "threefry": 0, **_rng_draws},
            "uneven_constraints": dict(_uneven_constraints),
            "moe_live_chunks": {
                "ops": _moe_live_chunks["ops"],
                "chunk_rows": dict(_moe_live_chunks["chunk_rows"])},
            "attention_band_grid": {
                "ops": _attention_band_grid["ops"],
                "steps": {k: list(v) for k, v in
                          _attention_band_grid["steps"].items()}},
            "tuning": {k: (round(v, 3) if isinstance(v, float) else v)
                       for k, v in _stats.items()},
        }


def reset_attribution():
    with _lock:
        _kernel_hits.clear()
        _dense_vjp_hits.clear()
        _rng_draws.clear()
        _uneven_constraints.clear()
        _moe_live_chunks.update(ops=0, chunk_rows={})
        _attention_band_grid.update(ops=0, steps={})
        _stats.update(_STATS_ZERO)


def cache_stats():
    """Entry count + path of the live cache (for tests/diagnostics)."""
    with _lock:
        _load_locked()
        return {"entries": len(_cache), "path": _cache_path,
                "searched": sum(1 for v in _cache.values()
                                if v.get("searched"))}


def clear_cache(forget_path=False):
    """Drop the in-memory cache (tests); the on-disk file is untouched.
    forget_path also resets the load marker so the next consult reloads
    from FLAGS_kernel_tune_cache."""
    global _cache, _cache_path
    with _lock:
        _cache = None if forget_path else {}
        if forget_path:
            _cache_path = None
