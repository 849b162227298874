"""TVM-style program autotuner (ROADMAP item 2c): search the discrete
PROGRAM knob space per (program-signature, shape-bucket), with a
persisted decision cache.

The knobs select between whole PROGRAMS:

* ``mesh_shape``        — (dp, mp) or (dp, mp, pp) training mesh, None
                          = no mesh (a rebuild knob: the builder stamps
                          the candidate mesh via annotate_spmd + the
                          train rule table, and slices it with
                          ``pipeline_program`` when a pp extent > 1 is
                          present; shapes the visible device count
                          cannot host are never tried)
* ``rule_table``        — partition rules under a mesh: the registered
                          "family" table vs "replicated" (dp-only —
                          params stay replicated, the batch feeds still
                          shard); searched only once a mesh is in play
* ``bf16_amp``          — the bf16_amp_pass rewrite on/off (a rebuild
                          knob: AMP must precede minimize, so searching
                          it needs a ``variants`` builder callback)
* ``remat``             — checkpoint-segment count (rebuild knob, same
                          reason; FLAGS_hbm_budget_bytes forces it
                          outside the tuner when memory, not time, is
                          the binding constraint)
* ``steps_per_dispatch``— K steps per device dispatch via
                          Executor.run_loop's compiled lax.scan (the
                          host-dispatch-tax knob; applies to
                          steady-state fixed-feed stepping: bench legs,
                          eval loops — run() drivers with per-step data
                          keep 1)
* ``comm_bucket_bytes`` — consult-only: a distributed bench can deposit
                          a searched value, the tuner itself never
                          times multi-process candidates
* ``spec_k``            — consult-only serving knob: the speculative
                          chunk width a serve bench measured best for
                          this (model, shape) — acceptance rate is
                          workload-dependent, so the tuner never times
                          it on synthetic feeds; None = engine default
* ``use_draft``         — consult-only serving knob: arm the draft
                          model at all ("self" / True / False / None);
                          deposited by a serving run, never searched
* ``prefix_chunk``      — consult-only serving knob: prefix-cache match
                          granularity (a multiple of the engine width);
                          None = engine default (== width)
* ``n_microbatches``    — consult-only pipeline knob: the microbatch
                          count M a pipeline bench measured best for
                          this (model, shape) under a pp mesh; the
                          bubble fraction (S-1)/(M+S-1) vs per-tick
                          efficiency trade is batch- and
                          schedule-dependent, so the tuner never times
                          it on synthetic feeds; None = S (one
                          microbatch per stage)

Search is greedy coordinate descent (knob order as listed, best value
kept before moving on) bounded by ``max_trials`` timings; each timing
jits the candidate program on synthetic operands and measures
steady-state steps/s.  Decisions persist as JSON at
``FLAGS_program_tune_cache`` keyed (signature | feed shape-bucket |
device kind; pow2 leading dims, exact feature dims), and
``FLAGS_program_autotune=0`` is the CI regime: consult-only, misses
return the all-defaults decision and never time anything.

Entry points: ``tune(program, feed_spec, ...)`` -> decision dict;
``tuned_flags(decision)`` -> the FLAGS_* mapping a driver applies.
"""

import hashlib
import threading
import time

import numpy as np

__all__ = [
    "DEFAULT_DECISION",
    "program_signature",
    "tune",
    "tuned_flags",
    "serving_knobs",
    "pipeline_knobs",
    "cache_stats",
    "clear_cache",
]

DEFAULT_DECISION = {
    "mesh_shape": None,          # (dp, mp) GSPMD mesh, None = no mesh
    "rule_table": "family",      # partition rules under a mesh:
    #                              "family" = the registered table,
    #                              "replicated" = params stay replicated
    #                              (dp-only sharding via the batch feeds)
    "bf16_amp": False,
    "remat": 0,
    "steps_per_dispatch": 1,
    "comm_bucket_bytes": None,   # consult-only knob
    # consult-only SERVING knobs (ServingEngine fast path): deposited by
    # the serve bench, merged under cached decisions like every new knob
    # (a committed CI cache predating them keeps validating), and never
    # searched — acceptance rate and prefix locality are properties of
    # the TRAFFIC, which synthetic feeds cannot represent
    "spec_k": None,              # None = engine default (min(4, width))
    "use_draft": None,           # None = off; "self" | True = self-draft
    "prefix_chunk": None,        # None = engine default (== width)
    # consult-only PIPELINE knob (pp mesh legs): deposited by
    # BENCH_SPMD_PP, consumed via pipeline_knobs(decision)
    "n_microbatches": None,      # None = pipeline default (M == S)
}

# search order: rebuild knobs first (they change the op mix) — the mesh
# before the rewrites that must compose with it — dispatch-schedule last
_KNOB_ORDER = ("mesh_shape", "rule_table", "bf16_amp", "remat",
               "steps_per_dispatch")

_lock = threading.RLock()
_cache = None
_cache_path = None
_stats = {"hits": 0, "misses": 0, "searches": 0, "search_ms": 0.0}


def _flag(name):
    from ..flags import get_flag

    return get_flag(name)


def program_signature(program):
    """Stable identity of a program's structure: the op type sequence of
    every block plus the persistable (name, shape, dtype) table, hashed.
    Deterministic across processes for the same build path (builders run
    under unique_name.guard), insensitive to feed VALUES — the shape
    side rides the cache key's shape bucket instead."""
    h = hashlib.sha1()
    for blk in program.blocks:
        for op in blk.ops:
            h.update(op.type.encode())
            h.update(b";")
        h.update(b"|")
    for name, v in sorted(program.global_block().vars.items()):
        if getattr(v, "persistable", False):
            h.update(("%s:%s:%s" % (name, v.shape, v.dtype)).encode())
    return h.hexdigest()[:16]


def _device_kind():
    """Stable device identity for cache keys; CPU runs are their own
    universe so a CI cache never leaks onto a real chip."""
    import jax

    try:
        d = jax.devices()[0]
    except RuntimeError:
        return "unknown"
    if d.platform != "tpu":
        return "interpret-%s" % d.platform
    return (getattr(d, "device_kind", "") or d.platform).replace(" ", "_")


def _shape_bucket(shapes):
    """Canonical bucket string: leading (row/batch) dims round up to the
    next power of two — one searched entry serves every batch in the
    bucket — while the last (feature) dim of each operand stays exact."""
    def pow2(n):
        return 1 << max(0, int(n) - 1).bit_length()

    return ",".join(
        "x".join([str(pow2(d)) for d in shape[:-1]]
                 + [str(int(d)) for d in shape[-1:]])
        for shape in shapes)


def _key(program, feed_spec):
    shapes = [shape for _, (shape, _dtype) in sorted(feed_spec.items())]
    return "|".join([program_signature(program), _shape_bucket(shapes),
                     _device_kind()])


def _entry_valid(v):
    return isinstance(v.get("decision"), dict)


def _load_locked():
    global _cache, _cache_path
    from ..utils.tune_cache import load_entries

    path = str(_flag("program_tune_cache") or "")
    if _cache is not None and path == _cache_path:
        return
    _cache_path = path
    _cache = load_entries(path, _entry_valid, "program tuning cache")


def _save_locked():
    # searched decisions only, merged with concurrent writers, atomic
    # replace (utils.tune_cache)
    from ..utils.tune_cache import save_entries

    save_entries(_cache_path, _cache, _entry_valid,
                 "program tuning cache")


def _synthesize_feeds(feed_spec, seed=0):
    rng = np.random.RandomState(seed)
    feeds = {}
    for name, (shape, dtype) in feed_spec.items():
        dt = np.dtype(str(dtype)) if str(dtype) != "bfloat16" else None
        if dt is not None and dt.kind in "iu":
            # small ids stay legal for any lookup table
            feeds[name] = rng.randint(0, 2, size=shape).astype(dt)
        elif dt is not None and dt.kind == "b":
            feeds[name] = rng.rand(*shape) > 0.5
        else:
            feeds[name] = (rng.rand(*shape) * 0.1).astype(
                dt or np.float32)
    return feeds


def tuned_flags(decision):
    """The FLAGS_* mapping a driver applies before running the tuned
    program (flag knobs only; rebuild knobs are baked into the program
    the ``variants`` callback returned, and steps_per_dispatch is the
    driver's run()/run_loop() choice).  No knob is a flag today."""
    return {}


def serving_knobs(decision):
    """The ServingEngine keyword mapping for a decision's consult-only
    serving knobs — the serve-side twin of tuned_flags.  Only knobs the
    decision actually pins appear (None stays with the engine default),
    so ``ServingEngine(exe, hp, **serving_knobs(d), ...)`` composes with
    explicit call-site overrides."""
    out = {}
    if decision.get("spec_k") is not None:
        out["spec_k"] = int(decision["spec_k"])
    ud = decision.get("use_draft")
    if ud:  # "self" / True -> self-draft; False/None -> leave off
        out["draft"] = "self"
    if decision.get("prefix_chunk") is not None:
        out["prefix_chunk"] = int(decision["prefix_chunk"])
    return out


def pipeline_knobs(decision):
    """The ``pipeline_program`` keyword mapping for a decision's
    consult-only pipeline knobs — the pp-side twin of serving_knobs.
    Only knobs the decision pins appear (None stays with the pipeline
    default M == S), so ``pipeline_program(main, mesh,
    **pipeline_knobs(d))`` composes with explicit call-site
    overrides."""
    out = {}
    if decision.get("n_microbatches") is not None:
        out["n_microbatches"] = int(decision["n_microbatches"])
    return out


def _candidates_for(knob, rebuild, program, best=None):
    from .remat import detect_segments

    if knob == "mesh_shape":
        # rebuild knob: the builder stamps the program for the candidate
        # dp x mp mesh (annotate_spmd + train rules), or slices it with
        # pipeline_program for a (dp, mp, pp) triple — only shapes the
        # visible device count can host are tried.  Builders that
        # predate the pp axis raise on a 3-tuple; the search skips the
        # failed candidate (the _measure_decision exception path)
        if rebuild is None:
            return []
        import jax

        n = len(jax.devices())
        flat = [(dp, mp) for dp, mp in ((2, 1), (1, 2), (2, 2))
                if dp * mp <= n]
        pp3 = [(dp, mp, pp)
               for dp, mp, pp in ((1, 1, 2), (2, 1, 2), (1, 1, 4))
               if dp * mp * pp <= n]
        return flat + pp3
    if knob == "rule_table":
        # only meaningful once a mesh is in play: without one the table
        # never resolves, so the candidate would re-time the baseline
        if rebuild is None or not (best or {}).get("mesh_shape"):
            return []
        return ["family", "replicated"]
    if knob == "bf16_amp":
        return [False, True] if rebuild is not None else []
    if knob == "remat":
        if rebuild is None:
            return []
        n = max(0, len(detect_segments(program)) - 1)
        return [0, n] if n else []
    if knob == "steps_per_dispatch":
        return [1, 8]
    return []


def _measure_decision(decision, program, startup, feed_spec, fetches,
                      rebuild, steps, warmup, seed):
    """steps/s of one candidate: (re)build under the rebuild knobs, jit
    on synthetic operands, time steady state."""
    import jax

    from .. import executor as executor_mod
    from ..core import scope as scope_mod
    from ..places import default_place

    main, startup_p, fetch_list = program, startup, fetches
    if rebuild is not None and (decision.get("bf16_amp")
                                or decision.get("remat")
                                or decision.get("mesh_shape")
                                or decision.get("rule_table",
                                                "family") != "family"):
        main, startup_p, fetch_list = rebuild(decision)
    scope = scope_mod.Scope()
    with scope_mod.scope_guard(scope):
        exe = executor_mod.Executor(default_place())
        if startup_p is not None:
            startup_p.random_seed = 1234
            exe.run(startup_p, scope=scope)
        feeds = _synthesize_feeds(feed_spec, seed)
        window = int(decision.get("steps_per_dispatch", 1) or 1)
        if window > 1:
            out = exe.run_loop(window, main, feed=feeds,
                               fetch_list=fetch_list,
                               scope=scope, return_numpy=False)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            out = exe.run_loop(window, main, feed=feeds,
                               fetch_list=fetch_list,
                               scope=scope, return_numpy=False)
            jax.block_until_ready(out)
            return window / (time.perf_counter() - t0)
        out = None
        for _ in range(max(1, warmup)):  # >= 1: the first run is
            # the compile; timing it would measure XLA, not the step
            out = exe.run(main, feed=feeds, fetch_list=fetch_list,
                          scope=scope, return_numpy=False)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(steps):
            out = exe.run(main, feed=feeds, fetch_list=fetch_list,
                          scope=scope, return_numpy=False)
        jax.block_until_ready(out)
        return steps / (time.perf_counter() - t0)


def tune(program, feed_spec, startup=None, fetches=None, rebuild=None,
         max_trials=12, steps=4, warmup=2, measure=None, seed=0):
    """Return the tuned knob decision for (program, feed shapes).

    feed_spec: {name: (shape, dtype)} — ``utils.memory_analysis.
    program_feed_specs`` derives it from the program's data vars.
    startup/fetches: the program's startup twin and fetch list; needed
    to TIME candidates (a consult-only call can omit them).
    rebuild: optional callable(decision) -> (main, startup, fetches)
    re-running the model builder under the decision's REBUILD knobs
    (bf16_amp, remat) — those rewrites must precede minimize, so the
    builder is their natural owner; without it they are not searched.
    measure: optional decision -> steps/s callable injected by tests;
    with it the search runs regardless of FLAGS_program_autotune.

    Cache hit -> cached decision.  Miss -> greedy coordinate-descent
    search when allowed (FLAGS_program_autotune and a timeable setup),
    else the all-defaults decision; either way the decision is recorded
    (and persisted when FLAGS_program_tune_cache names a file) so it is
    made once per (program signature, shape bucket, device kind)."""
    with _lock:
        _load_locked()
        key = _key(program, feed_spec)
        hit = _cache.get(key)
        if hit is not None:
            _stats["hits"] += 1
            d = dict(DEFAULT_DECISION)
            d.update(hit["decision"])
            if isinstance(d.get("mesh_shape"), list):  # JSON round-trip
                d["mesh_shape"] = tuple(d["mesh_shape"])
            return d
        _stats["misses"] += 1

    can_search = measure is not None or (
        bool(_flag("program_autotune"))
        and startup is not None and fetches is not None)
    entry = {"decision": dict(DEFAULT_DECISION), "searched": False,
             "search_ms": 0.0}
    if can_search:
        if measure is None:
            def measure(decision):
                return _measure_decision(
                    decision, program, startup, feed_spec, fetches,
                    rebuild, steps, warmup, seed)

        t0 = time.perf_counter()
        best = dict(DEFAULT_DECISION)
        trials = 0
        try:
            best_sps = measure(dict(best))
            baseline_sps = best_sps
            trials += 1
            for knob in _KNOB_ORDER:
                if trials >= max_trials:
                    break
                for cand in _candidates_for(knob, rebuild, program, best):
                    if cand == best.get(knob):
                        continue  # already measured as part of `best`
                    if trials >= max_trials:
                        break
                    d = dict(best)
                    d[knob] = cand
                    try:
                        sps = measure(d)
                    except Exception as e:  # candidate failed: skip it
                        import sys

                        sys.stderr.write(
                            "autotune: candidate %s=%r failed (%r); "
                            "skipped\n" % (knob, cand, e))
                        continue
                    trials += 1
                    if sps > best_sps:
                        best, best_sps = d, sps
            ms = (time.perf_counter() - t0) * 1e3
            entry = {
                "decision": best,
                "searched": True,
                "search_ms": round(ms, 3),
                "trials": trials,
                "baseline_steps_per_s": round(float(baseline_sps), 4),
                "best_steps_per_s": round(float(best_sps), 4),
            }
        except Exception as e:
            import sys

            sys.stderr.write(
                "autotune: search failed (%r); seeding the all-defaults "
                "decision\n" % (e,))

    with _lock:
        _cache[key] = entry
        if entry["searched"]:
            _stats["searches"] += 1
            _stats["search_ms"] += entry["search_ms"]
            _save_locked()
    d = dict(DEFAULT_DECISION)
    d.update(entry["decision"])
    if isinstance(d.get("mesh_shape"), list):  # JSON round-trip
        d["mesh_shape"] = tuple(d["mesh_shape"])
    return d


def cache_stats():
    with _lock:
        _load_locked()
        return {
            "entries": len(_cache),
            "path": _cache_path,
            "searched": sum(1 for v in _cache.values()
                            if v.get("searched")),
            "stats": dict(_stats),
        }


def clear_cache(forget_path=False):
    """Drop the in-memory cache (tests); the on-disk file is untouched.
    forget_path also resets the load marker so the next consult reloads
    from FLAGS_program_tune_cache."""
    global _cache, _cache_path
    with _lock:
        _cache = None if forget_path else {}
        if forget_path:
            _cache_path = None
        _stats.update({"hits": 0, "misses": 0, "searches": 0,
                       "search_ms": 0.0})
