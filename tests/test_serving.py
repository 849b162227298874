"""Continuous-batching serving engine (paddle_tpu/serving, docs/SERVING.md
§5, §8): slot-pool churn exactness, the compiles-once contract, per-slot
machinery unit tests, the speculative-decoding + prefix-cache fast path,
and the slow-marked bf16-KV / weight-only-int8 engine variants."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import gpt2
from paddle_tpu.models.decode_cache import (
    filtered_probs_rows,
    fold_in_seed,
    make_row_copy_program,
    make_slot_reset_program,
    sample_rows_keyed,
)
from paddle_tpu.serving import (
    PrefixCache,
    Request,
    ServingEngine,
    make_poisson_trace,
    make_prefix_trace,
    serve_one_at_a_time,
)


class TinyHP(gpt2.GPT2Config):
    vocab_size = 61
    n_ctx = 32
    d_model = 32
    n_layer = 2
    n_head = 4
    dropout = 0.0


_ENGINE_CACHE = {}


class _PinnedScopeExecutor(fluid.Executor):
    """Executor that defaults to a dedicated persistent scope instead of
    the global one.  The conftest `fresh_programs` fixture swaps the
    GLOBAL scope per test, and the XLA compile cache is keyed on the
    scope id — pinning keeps a memoized engine's weights AND its
    compiled executables valid across tests."""

    def __init__(self, place, scope):
        super().__init__(place)
        self._pinned_scope = scope

    def run(self, *args, **kw):
        if kw.get("scope") is None:
            kw["scope"] = self._pinned_scope
        return super().run(*args, **kw)


def _make_engine(hp=TinyHP, n_slots=4, width=4, t_max=24, seed=7, **kw):
    """Engine over randomly initialized tiny-GPT2 weights (the logits
    program's startup provides them through the shared names).

    MEMOIZED per config: run() fully resets an engine (counters,
    results, cache startups), so tests with the same (hp, shape, seed,
    kwargs) share one compiled engine — living in its own
    pinned scope, see _PinnedScopeExecutor — instead of paying ~4s of
    tracing each, the single biggest cost in this file.  Not cached:
    engines with `prefix_rows` (a PrefixCache keeps registered rows
    ACROSS runs by design, so sharing would leak registrations between
    tests)."""
    key = (hp.__name__, n_slots, width, t_max, seed,
           tuple(sorted(kw.items())))
    cacheable = not kw.get("prefix_rows")
    if cacheable and key in _ENGINE_CACHE:
        exe, eng = _ENGINE_CACHE[key]
        eng.queue_depth = kw.get("queue_depth")  # undo test mutations
        return exe, eng
    _, lm_startup, _, _ = gpt2.gpt2_logits_program(hp, seq_len=t_max)
    if cacheable:
        exe = _PinnedScopeExecutor(fluid.CPUPlace(), fluid.Scope())
    else:
        exe = fluid.Executor(fluid.CPUPlace())
    lm_startup.random_seed = seed
    exe.run(lm_startup)
    eng = ServingEngine(exe, hp, n_slots=n_slots, width=width,
                        t_max=t_max, **kw)
    if cacheable:
        _ENGINE_CACHE[key] = (exe, eng)
    return exe, eng


def _churn_trace(vocab, greedy_only=False, seed=0):
    """8 requests > 4 slots with STAGGERED arrivals and mixed prompt/
    output lengths — forces admission churn and slot reuse."""
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(8):
        sampled = (not greedy_only) and i % 2 == 1
        reqs.append(Request(
            rid=i,
            prompt=rng.randint(1, vocab, int(rng.randint(2, 11))),
            max_new_tokens=int(rng.randint(3, 9)),
            temperature=0.8 + 0.1 * (i % 3) if sampled else 1.0,
            top_k=[0, 8, 16][i % 3] if sampled else 0,
            top_p=0.9 if sampled and i % 4 == 1 else 1.0,
            seed=1000 + i if sampled else None,
            arrival=float(i) * 0.9,
        ))
    return reqs


# ---------------------------------------------------------------------------
# unit: the per-slot machinery
# ---------------------------------------------------------------------------
def test_slot_cache_write_per_row_masked():
    """Row b writes width[b] columns at pos[b]; columns beyond width (or
    past the cache) are dropped, never clamped onto neighbors."""
    B, H, W, T, D = 3, 2, 4, 8, 2
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        cache = layers.data("cache", shape=[B, H, T, D], dtype="float32",
                            append_batch_size=False)
        new = layers.data("new", shape=[B, H, W, D], dtype="float32",
                          append_batch_size=False)
        pos = layers.data("pos", shape=[B], dtype="int64",
                          append_batch_size=False)
        width = layers.data("width", shape=[B], dtype="int64",
                            append_batch_size=False)
        out = layers.slot_cache_write(cache, new, pos, width)
    rng = np.random.RandomState(0)
    c = rng.rand(B, H, T, D).astype("float32")
    n = rng.rand(B, H, W, D).astype("float32")
    p = np.array([0, 3, 6], "int64")   # row 2 would run past T=8
    w = np.array([4, 1, 4], "int64")
    exe = fluid.Executor(fluid.CPUPlace())
    (got,) = exe.run(prog, feed={"cache": c, "new": n, "pos": p,
                                 "width": w}, fetch_list=[out])
    ref = c.copy()
    for b in range(B):
        for i in range(int(w[b])):
            if p[b] + i < T:
                ref[b, :, p[b] + i] = n[b, :, i]
    np.testing.assert_array_equal(np.asarray(got), ref)


def test_slot_reset_program_zeroes_only_masked_slots():
    B, H, T, D = 4, 2, 6, 3
    prog = make_slot_reset_program([("pool_cache", (B, H, T, D))], B)
    scope = fluid.Scope()
    rng = np.random.RandomState(1)
    init = rng.rand(B, H, T, D).astype("float32")
    with fluid.scope_guard(scope):
        scope.set("pool_cache", init.copy())
        exe = fluid.Executor(fluid.CPUPlace())
        keep = np.array([1.0, 0.0, 1.0, 0.0], "float32")
        exe.run(prog, feed={"slot_keep": keep}, fetch_list=[])
        got = np.asarray(scope.find_var("pool_cache"))
    np.testing.assert_array_equal(got[0], init[0])
    np.testing.assert_array_equal(got[2], init[2])
    assert (got[1] == 0).all() and (got[3] == 0).all()


def test_keyed_sampling_is_pure_per_request():
    """A row's draw depends only on (seed, step) — not on neighbors,
    slot order, or batch size (what makes churn exactness testable)."""
    rng = np.random.RandomState(0)
    probs = rng.dirichlet(np.ones(16), size=4)
    seeds = [11, 22, 33, 44]
    steps = [0, 5, 2, 7]
    base = sample_rows_keyed(probs, seeds, steps)
    # permute the batch: each request's draw rides along unchanged
    perm = [2, 0, 3, 1]
    permuted = sample_rows_keyed(probs[perm], [seeds[i] for i in perm],
                                 [steps[i] for i in perm])
    for j, i in enumerate(perm):
        assert permuted[j] == base[i]
    # solo (batch of one) equals the pooled draw
    for i in range(4):
        solo = sample_rows_keyed(probs[i:i + 1], [seeds[i]], [steps[i]])
        assert solo[0] == base[i]
    # distinct steps give independent draws deterministically
    again = sample_rows_keyed(probs, seeds, steps)
    np.testing.assert_array_equal(base, again)
    assert fold_in_seed(1, 2) != fold_in_seed(2, 1)
    assert fold_in_seed(1, 2) == fold_in_seed(1, 2)


def test_filtered_probs_rows_vectorized_bit_identical_to_row_loop():
    """The engine's batched sampler (PR 9's "loops per row; vectorize
    if pools grow" limit closed): the vectorized filtered_probs_rows is
    BIT-identical to composing filtered_probs row by row, across
    heterogeneous temperature/top-k/top-p mixes — including rows whose
    solo run skips the top-k and/or top-p branches entirely (a skipped
    renormalization must stay skipped, or bits drift)."""
    from paddle_tpu.models.decode_cache import filtered_probs

    rng = np.random.RandomState(7)
    logits = (rng.randn(8, 23) * 3).astype("float32")
    temps = [1.0, 0.7, 1.3, 1e-9, 1.0, 0.85, 2.0, 1.0]
    ks = [0, 5, 23, 0, 1, 8, 0, 40]       # off / partial / full / >vocab
    ps = [1.0, 0.9, 1.0, 0.5, 1.0, 0.95, 0.3, 1.0]
    got = filtered_probs_rows(logits, temps, ks, ps)
    for i in range(8):
        ref = filtered_probs(logits[i:i + 1], float(temps[i]),
                             int(ks[i]), float(ps[i]))
        np.testing.assert_array_equal(got[i], ref[0],
                                      err_msg="row %d diverged" % i)


def test_poisson_trace_deterministic():
    a = make_poisson_trace(6, 1.5, (2, 8), (3, 6), 100, seed=42)
    b = make_poisson_trace(6, 1.5, (2, 8), (3, 6), 100, seed=42)
    assert len(a) == 6
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra.prompt, rb.prompt)
        assert (ra.arrival, ra.max_new_tokens, ra.seed, ra.temperature,
                ra.top_k, ra.top_p) == (rb.arrival, rb.max_new_tokens,
                                        rb.seed, rb.temperature, rb.top_k,
                                        rb.top_p)
    arr = [r.arrival for r in a]
    assert arr == sorted(arr) and arr[0] > 0


# ---------------------------------------------------------------------------
# the ragged step program against the existing decode references
# ---------------------------------------------------------------------------
def test_ragged_step_matches_reference_decode_paths():
    """A solo request through the pooled ragged program emits the same
    greedy tokens as the one-token cached chain AND the full re-encode
    — the ragged write/mask machinery changes scheduling, not math."""
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        lm_main, lm_startup, _, lm_fetch = gpt2.gpt2_logits_program(
            TinyHP, seq_len=24)
        step_main, cst, _, sfetch, _ = gpt2.gpt2_decode_step_program(
            TinyHP, batch=1, t_max=24)
        exe = fluid.Executor(fluid.CPUPlace())
        lm_startup.random_seed = 7
        exe.run(lm_startup)
        prompt = np.random.RandomState(3).randint(
            1, TinyHP.vocab_size, (1, 6)).astype("int64")
        ref = gpt2.greedy_generate_cached(
            exe, step_main, cst, sfetch, prompt, 8)[0, 6:]
        full = gpt2.greedy_generate(exe, lm_main, lm_fetch, prompt, 8)[0, 6:]
        eng = ServingEngine(exe, TinyHP, n_slots=2, width=4, t_max=24)
        got, _ = eng.run_solo(Request(0, prompt[0], 8))
        np.testing.assert_array_equal(got, np.asarray(ref))
        np.testing.assert_array_equal(got, np.asarray(full))


# ---------------------------------------------------------------------------
# tier-1 churn exactness (the engine's core contract)
# ---------------------------------------------------------------------------
def _assert_churn_exact(eng, reqs):
    results, stats = eng.run(list(reqs))
    assert stats["finished"] == len(reqs)
    # real churn happened: more requests than slots, staggered admission
    assert stats["admitted"] == len(reqs) > eng.n_slots
    admits = sorted(results[r.rid]["admit_step"] for r in reqs)
    assert admits[-1] > admits[0], admits
    for r in reqs:
        solo, _ = eng.run_solo(r)
        np.testing.assert_array_equal(
            results[r.rid]["tokens"], solo,
            err_msg="request %r pooled tokens != solo tokens" % r.rid)
    return results, stats


def test_engine_churn_exactness_greedy():
    """Staggered arrivals + slot reuse + early EOS: every request's
    greedy stream is bit-identical to its solo run."""
    _, eng = _make_engine()
    reqs = _churn_trace(TinyHP.vocab_size, greedy_only=True)
    results, _ = _assert_churn_exact(eng, reqs)
    # EARLY-EOS leg: stop request 0 at a token its own stream emits —
    # the slot must free mid-flight and the truncated stream must still
    # match the solo run with the same eos
    base = results[0]["tokens"]
    assert base.size >= 3
    eos = int(base[1])
    r0 = Request(100, reqs[0].prompt, reqs[0].max_new_tokens,
                 eos_id=eos, arrival=0.0)
    churn = [r0] + [Request(101 + i, r.prompt, r.max_new_tokens,
                            arrival=r.arrival)
                    for i, r in enumerate(reqs[1:4])]
    res2, _ = eng.run(churn)
    assert res2[100]["tokens"].size < base.size  # actually stopped early
    assert int(res2[100]["tokens"][-1]) == eos
    solo0, _ = eng.run_solo(r0)
    np.testing.assert_array_equal(res2[100]["tokens"], solo0)


def test_engine_churn_exactness_sampled():
    """Per-request seeded sampling with heterogeneous temperature/
    top-k/top-p: the sample stream is a pure function of (request,
    step), so pooled == solo bit-for-bit under churn."""
    _, eng = _make_engine()
    reqs = _churn_trace(TinyHP.vocab_size, greedy_only=False, seed=5)
    assert any(not r.greedy for r in reqs)
    _assert_churn_exact(eng, reqs)


def test_engine_compiles_once_across_occupancy():
    """The no-retrace contract: after the first full step (startup +
    reset + step program traced), ANY occupancy change — admission,
    eviction, slot reuse, drain — reuses the same executables."""
    exe, eng = _make_engine()
    warm = [Request(900, np.array([1, 2, 3]), 3, arrival=0.0),
            Request(901, np.array([4, 5]), 2, arrival=0.0)]
    eng.run(warm)  # compiles: cache_startup, reset, step
    baseline = exe.compile_count
    reqs = _churn_trace(TinyHP.vocab_size, greedy_only=True, seed=9)
    results, stats = eng.run(reqs)
    assert stats["finished"] == len(reqs)
    assert exe.compile_count == baseline, (
        "occupancy churn retraced the serving step: %d -> %d"
        % (baseline, exe.compile_count))
    # and the engine's own stats agree
    assert stats["compile_count"] == baseline


def test_serve_one_at_a_time_baseline_contract():
    """The A/B baseline serves the identical trace with identical
    tokens (it IS the solo reference), one request at a time."""
    _, eng = _make_engine()
    reqs = _churn_trace(TinyHP.vocab_size, greedy_only=True, seed=3)[:4]
    results, _ = eng.run(list(reqs))
    base_results, base_stats = serve_one_at_a_time(
        eng, reqs, arrival_step_seconds=0.0)
    assert base_stats["new_tokens"] == sum(
        r["tokens"].size for r in results.values())
    for r in reqs:
        np.testing.assert_array_equal(results[r.rid]["tokens"],
                                      base_results[r.rid]["tokens"])


def test_engine_rejects_oversized_request():
    _, eng = _make_engine(t_max=16)
    with pytest.raises(ValueError):
        eng.submit(Request(0, np.arange(1, 10), 10))  # 9 + 10 > 17


# ---------------------------------------------------------------------------
# the decode/prefill fast path: speculative decoding + prefix KV reuse
# (docs/SERVING.md §8)
# ---------------------------------------------------------------------------
def test_row_copy_program_gathers_only_taken_rows():
    """make_row_copy_program: dst row i <- src[copy_src_rows[i]] where
    copy_take[i]=1, untouched where copy_keep[i]=1 — any assignment
    through ONE executable (ids/masks are feeds)."""
    R, B, H, T, D = 3, 4, 2, 6, 3
    prog = make_row_copy_program(
        [("pfx_c", (R, H, T, D), "slot_c", (B, H, T, D))], B)
    scope = fluid.Scope()
    rng = np.random.RandomState(2)
    src = rng.rand(R, H, T, D).astype("float32")
    dst = rng.rand(B, H, T, D).astype("float32")
    with fluid.scope_guard(scope):
        scope.set("pfx_c", src.copy())
        scope.set("slot_c", dst.copy())
        exe = fluid.Executor(fluid.CPUPlace())
        take = np.array([1.0, 0.0, 1.0, 0.0], "float32")
        exe.run(prog, feed={
            "copy_src_rows": np.array([2, 0, 1, 0], "int64"),
            "copy_take": take, "copy_keep": 1.0 - take}, fetch_list=[])
        got = np.asarray(scope.find_var("slot_c"))
    np.testing.assert_array_equal(got[0], src[2])
    np.testing.assert_array_equal(got[1], dst[1])
    np.testing.assert_array_equal(got[2], src[1])
    np.testing.assert_array_equal(got[3], dst[3])


def test_prefix_cache_match_chunk_floor_dedup_and_lru():
    """PrefixCache host index: longest-match floored to the chunk and
    capped at len(prompt)-1; ties prefer the lower row; exact
    re-registration dedups to the same row; a full pool evicts the
    least-recently-matched row."""
    pc = PrefixCache(rows=2, chunk=4)
    a = np.arange(100, 112, dtype="int64")      # 12 tokens = 3 chunks
    b = np.arange(200, 208, dtype="int64")      # 8 tokens = 2 chunks
    ra, fresh_a = pc.assign(a)
    rb, fresh_b = pc.assign(b)
    assert fresh_a and fresh_b and ra != rb
    # exact dedup: same tokens -> same row, no new registration
    assert pc.assign(a.copy()) == (ra, False)
    # longest match, chunk-floored: 10 shared tokens -> 8
    prompt = np.concatenate([a[:10], np.array([7, 7, 7], "int64")])
    row, L = pc.match(prompt)
    assert (row, L) == (ra, 8)
    # cap at len(prompt)-1: a prompt that IS the prefix must still
    # dispatch its last token through prefill (chunk floor: 12 -> 8)
    row, L = pc.match(a)
    assert (row, L) == (ra, 8)
    # sub-chunk overlap is a miss
    assert pc.match(np.array([100, 101, 9, 9, 9], "int64")) == (None, 0)
    # LRU eviction: touch row a, then a third registration evicts b
    pc.touch(ra, 8)
    c = np.arange(300, 308, dtype="int64")
    rc, fresh_c = pc.assign(c)
    assert fresh_c and rc == rb and pc.evictions == 1
    assert pc.match(np.concatenate([b, b[:1]]))[0] is None
    assert pc.match(np.concatenate([c, c[:1]])) == (rc, 8)


def _spec_kwargs():
    """SELF-draft speculation: the draft shares the target's weights —
    the machinery under test (draft rounds, widened verify, keyed
    accept/reject) is identical to a separate draft checkpoint's."""
    return dict(draft="self", spec_k=3)


def test_spec_churn_exactness_greedy_and_early_eos():
    """Speculation on, greedy churn (8 reqs > 4 slots, staggered):
    pooled == solo on the SPEC engine, and greedy spec == the plain
    non-spec engine bit-for-bit (verify-chunk argmax is prefix-pure, so
    acceptance/rejection cannot move the stream).  Early-EOS leg: an
    accepted token hitting eos mid-round discards the rest of the round
    and frees the slot."""
    _, eng = _make_engine(**_spec_kwargs())
    reqs = _churn_trace(TinyHP.vocab_size, greedy_only=True)
    results, stats = _assert_churn_exact(eng, reqs)
    assert stats["spec_rounds"] > 0 and stats["spec_proposed"] > 0
    assert 0.0 < stats["accept_rate"] <= 1.0
    # greedy spec == the plain engine's streams (fresh weights, same
    # seed) — speculation is a scheduling change, never a math change
    _, plain = _make_engine()
    for r in reqs:
        solo, _ = plain.run_solo(r)
        np.testing.assert_array_equal(
            results[r.rid]["tokens"], solo,
            err_msg="rid %r: greedy spec diverged from non-spec" % r.rid)
    # early-EOS mid-round: stop request 0 at its own second token
    base = results[0]["tokens"]
    eos = int(base[1])
    r0 = Request(100, reqs[0].prompt, reqs[0].max_new_tokens,
                 eos_id=eos, arrival=0.0)
    res2, _ = eng.run([r0] + [Request(101, reqs[1].prompt, 4,
                                      arrival=0.0)])
    assert res2[100]["tokens"].size < base.size
    assert int(res2[100]["tokens"][-1]) == eos
    solo0, _ = eng.run_solo(r0)
    np.testing.assert_array_equal(res2[100]["tokens"], solo0)


def test_spec_churn_exactness_sampled():
    """Speculation on, per-request seeded sampling: every token is a
    pure function of (seed, global token index, token prefix) via the
    tag-keyed propose/accept/residual draws — so pooled == solo under
    churn, independent of neighbors, admission order, or which step of
    a draft round emitted it."""
    _, eng = _make_engine(**_spec_kwargs())
    reqs = _churn_trace(TinyHP.vocab_size, greedy_only=False, seed=5)
    assert any(not r.greedy for r in reqs)
    results, stats = _assert_churn_exact(eng, reqs)
    assert stats["spec_proposed"] > 0
    # per-request acceptance counters ride the results
    for r in reqs:
        assert 0.0 <= results[r.rid]["accept_rate"] <= 1.0
        if results[r.rid]["spec_proposed"]:
            assert results[r.rid]["spec_accepted"] <= \
                results[r.rid]["spec_proposed"]
    # deterministic replay: the same trace re-serves byte-identically
    again, _ = eng.run([Request(
        rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new_tokens,
        temperature=r.temperature, top_k=r.top_k, top_p=r.top_p,
        seed=r.seed, arrival=r.arrival) for r in reqs])
    for r in reqs:
        np.testing.assert_array_equal(results[r.rid]["tokens"],
                                      again[r.rid]["tokens"])


def test_spec_compiles_once_across_occupancy():
    """The no-retrace contract with speculation armed: draft rounds,
    widened verify chunks, and acceptance-dependent advance are all
    feed-VALUE changes over the same executables (draft program, target
    program, resets) — occupancy churn never retraces."""
    exe, eng = _make_engine(**_spec_kwargs())
    warm = [Request(900, np.array([1, 2, 3]), 3, arrival=0.0),
            Request(901, np.array([4, 5]), 2, arrival=0.0)]
    eng.run(warm)
    baseline = exe.compile_count
    reqs = _churn_trace(TinyHP.vocab_size, greedy_only=False, seed=9)
    results, stats = eng.run(reqs)
    assert stats["finished"] == len(reqs)
    assert exe.compile_count == baseline, (
        "speculative churn retraced: %d -> %d"
        % (baseline, exe.compile_count))


def _prefix_trace_and_template(n=6, seed=21):
    """n requests, 4 sharing one 8-token template prefix (2 chunks at
    width 4), mixed greedy/sampled — the engine-level prefix A/B."""
    rng = np.random.RandomState(seed)
    tmpl = rng.randint(1, TinyHP.vocab_size, 8).astype("int64")
    reqs = []
    for i in range(n):
        tail = rng.randint(1, TinyHP.vocab_size,
                           int(rng.randint(2, 5))).astype("int64")
        prompt = (np.concatenate([tmpl, tail]) if i < 4
                  else rng.randint(1, TinyHP.vocab_size,
                                   6 + tail.size).astype("int64"))
        reqs.append(Request(
            rid=i, prompt=prompt, max_new_tokens=int(rng.randint(3, 7)),
            temperature=0.9 if i % 2 else 1.0,
            top_k=8 if i % 2 else 0,
            seed=500 + i if i % 2 else None,
            arrival=float(i) * 0.5))
    return reqs, tmpl


def test_prefix_hit_stream_bit_identical_to_cold_with_fewer_chunks():
    """ACCEPTANCE: registering the template changes WHICH cache rows
    prefill dispatches (load-then-resume at the match boundary) but not
    one byte of any stream — prefix-hit == cold, with the hit requests'
    prefill chunks gone from the dispatch count.  The cold leg uses the
    shared PLAIN engine (prefix counters exist on every engine), and the
    register-time validation rules (chunk flooring, dedup, mining) are
    checked on the same warm engine after its run — one engine build
    instead of three."""
    _, cold = _make_engine()
    reqs, tmpl = _prefix_trace_and_template()
    cold_res, cold_stats = cold.run(list(reqs))
    assert cold_stats["prefix_hits"] == 0  # no cache at all

    _, warm = _make_engine(prefix_rows=2)
    row = warm.register_prefix(tmpl)
    assert row is not None
    assert warm.register_prefix(tmpl) == row  # dedup, no re-prefill
    warm_res, warm_stats = warm.run(list(reqs))
    assert warm_stats["prefix_hits"] == 4
    assert warm_stats["prefix_misses"] == 2
    assert warm_stats["prefix_tokens_reused"] == 4 * 8
    # 2 chunks of the template skipped per hit request
    assert cold_stats["prefill_chunks"] - warm_stats["prefill_chunks"] \
        == 4 * 2
    for r in reqs:
        np.testing.assert_array_equal(
            cold_res[r.rid]["tokens"], warm_res[r.rid]["tokens"],
            err_msg="rid %r: prefix-hit stream != cold stream" % r.rid)
        assert warm_res[r.rid]["prefix_len"] == (8 if r.rid < 4 else 0)
    # solo exactness holds on the prefix engine too
    for r in reqs:
        solo, _ = warm.run_solo(r)
        np.testing.assert_array_equal(warm_res[r.rid]["tokens"], solo)

    # -- register_prefix floors to chunk and validates ------------------
    # (same engine, now idle; width 4 -> chunk 4)
    # shorter than one chunk: nothing to register
    assert warm.register_prefix(np.array([1, 2, 3], "int64")) is None
    # 10 tokens floor to 8; matching reflects the floored registration
    row = warm.register_prefix(np.arange(1, 11, dtype="int64"))
    assert row is not None
    m_row, L = warm.prefix.match(np.arange(1, 13, dtype="int64"))
    assert (m_row, L) == (row, 8)
    # observe_prefixes mines shared openings from a request batch
    # (2 rows already resident: mining the third exercises LRU eviction)
    reqs33, tmpl33 = _prefix_trace_and_template(seed=33)
    got = warm.observe_prefixes(reqs33, min_count=2)
    assert got, "4 requests share the template: it must be mined"
    assert any(np.array_equal(t, tmpl33)
               for t in warm.prefix.registered().values())


def test_spec_plus_prefix_churn_exactness():
    """The whole fast path at once: self-draft speculation + prefix KV
    reuse (both banks: a prefix hit must resume the DRAFT distribution
    bit-exactly too, or sampled accept/reject draws fork) under churn —
    every stream equals its solo run, zero retraces after warmup."""
    exe, eng = _make_engine(prefix_rows=2, **_spec_kwargs())
    reqs, tmpl = _prefix_trace_and_template(n=8, seed=17)
    eng.register_prefix(tmpl)
    results, stats = eng.run(list(reqs))
    assert stats["finished"] == len(reqs)
    assert stats["prefix_hits"] == 4 and stats["spec_proposed"] > 0
    baseline = exe.compile_count
    for r in reqs:
        solo, _ = eng.run_solo(r)
        np.testing.assert_array_equal(
            results[r.rid]["tokens"], solo,
            err_msg="rid %r: spec+prefix pooled != solo" % r.rid)
    assert exe.compile_count == baseline, "solo replays retraced"


def test_prefix_trace_generator_deterministic_and_prefix_heavy():
    reqs, prefixes = make_prefix_trace(
        20, rate=1.0, n_prefixes=2, prefix_len=8, tail_len_range=(2, 5),
        out_len_range=(3, 6), vocab_size=61, seed=9, reuse_fraction=0.8)
    reqs2, prefixes2 = make_prefix_trace(
        20, rate=1.0, n_prefixes=2, prefix_len=8, tail_len_range=(2, 5),
        out_len_range=(3, 6), vocab_size=61, seed=9, reuse_fraction=0.8)
    assert len(reqs) == 20 and len(prefixes) == 2
    for a, b in zip(reqs, reqs2):
        np.testing.assert_array_equal(a.prompt, b.prompt)
        assert (a.arrival, a.seed, a.max_new_tokens) == \
            (b.arrival, b.seed, b.max_new_tokens)
    for p, q in zip(prefixes, prefixes2):
        np.testing.assert_array_equal(p, q)
    hits = sum(any(np.array_equal(r.prompt[:8], p) for p in prefixes)
               for r in reqs)
    assert hits >= 10, "trace is not prefix-heavy"


def test_autotune_serving_knobs_consult_only():
    """The serving knobs ride the program-tuner's decision record as
    CONSULT-ONLY values: defaults are None (engine defaults), they are
    never searched, a cached decision predating them merges them in,
    and serving_knobs() maps a pinned decision onto ServingEngine
    kwargs."""
    from paddle_tpu.transpiler.autotune import (DEFAULT_DECISION,
                                                _KNOB_ORDER,
                                                serving_knobs)

    for k in ("spec_k", "use_draft", "prefix_chunk"):
        assert k in DEFAULT_DECISION and DEFAULT_DECISION[k] is None
        assert k not in _KNOB_ORDER  # never searched
    assert serving_knobs(dict(DEFAULT_DECISION)) == {}
    d = dict(DEFAULT_DECISION)
    d.update({"spec_k": 3, "use_draft": "self", "prefix_chunk": 8})
    assert serving_knobs(d) == {"spec_k": 3, "draft": "self",
                                "prefix_chunk": 8}
    # an OLD cached decision (no serving keys) still resolves: the
    # merge-under-defaults discipline keeps committed caches valid
    old = {k: v for k, v in DEFAULT_DECISION.items()
           if k not in ("spec_k", "use_draft", "prefix_chunk")}
    merged = dict(DEFAULT_DECISION)
    merged.update(old)
    assert serving_knobs(merged) == {}


# ---------------------------------------------------------------------------
# slow-marked engine variants
# ---------------------------------------------------------------------------
@pytest.mark.slow  # second engine compile per variant; rides scripts/ci.sh --full
def test_engine_bf16_kv_churn_exactness():
    """bf16 KV pool: engine-vs-solo equality still holds bit-for-bit
    (both run the SAME bf16 program); vs the f32 chain bf16 stays a
    documented approximation, not asserted here."""
    _, eng = _make_engine(cache_dtype="bfloat16")
    reqs = _churn_trace(TinyHP.vocab_size, greedy_only=False, seed=11)
    _assert_churn_exact(eng, reqs)


@pytest.mark.slow  # second engine compile per variant; rides scripts/ci.sh --full
def test_engine_weight_only_int8_churn_exactness():
    """Weight-only int8 serving step (per-row embedding scales +
    dequant-fused matmuls): churn exactness holds through the
    quantized program."""
    _, eng = _make_engine(quantize_int8=True)
    reqs = _churn_trace(TinyHP.vocab_size, greedy_only=True, seed=13)
    _assert_churn_exact(eng, reqs)


# ---------------------------------------------------------------------------
# admission control: bounded wait queue + per-request deadlines
# ---------------------------------------------------------------------------
def test_admission_queue_depth_rejects_overflow_loudly():
    """An arrival that finds `queue_depth` requests already waiting is
    rejected with a terminal REJECTED_QUEUE_FULL — the wait queue can
    never grow past the bound — while every ADMITTED request's tokens
    stay bit-identical to its solo run (the exactness contract is
    untouched by rejections)."""
    _, eng = _make_engine(n_slots=2)
    eng.queue_depth = 1
    # 5 simultaneous arrivals into 2 slots + depth-1 queue: 3 serve,
    # 2 reject
    reqs = [Request(i, np.array([1 + i, 2, 3]), 4, arrival=0.0)
            for i in range(5)]
    results, stats = eng.run(list(reqs))
    statuses = {r.rid: results[r.rid]["status"] for r in reqs}
    assert sorted(statuses.values()) == [
        "OK", "OK", "OK", "REJECTED_QUEUE_FULL", "REJECTED_QUEUE_FULL"], \
        statuses
    # arrival order wins: the first three (two slots + one queue place)
    assert [statuses[i] for i in range(3)] == ["OK"] * 3
    assert results[3]["tokens"].size == 0
    assert stats["rejected"] == 2 and stats["finished"] == 3
    # admitted requests still match their solo runs exactly
    for i in range(3):
        solo, _ = eng.run_solo(reqs[i])
        np.testing.assert_array_equal(results[i]["tokens"], solo)


def test_deadline_expires_queued_request():
    """A request whose deadline lapses while WAITING is evicted with a
    terminal status (zero tokens) instead of serving stale work; the
    slot-holders are untouched."""
    _, eng = _make_engine(n_slots=1)
    long_req = Request(0, np.array([1, 2]), 8, arrival=0.0)
    # arrives at 0 behind a busy slot, must finish within 2 steps —
    # impossible while queued
    waiter = Request(1, np.array([3, 4]), 2, arrival=0.0, deadline=2)
    results, stats = eng.run([long_req, waiter])
    assert results[0]["status"] == "OK"
    assert results[1]["status"] == "DEADLINE_EXPIRED"
    assert results[1]["tokens"].size == 0
    assert stats["expired"] == 1
    # the survivor is exact
    solo, _ = eng.run_solo(long_req)
    np.testing.assert_array_equal(results[0]["tokens"], solo)


def test_deadline_expires_mid_decode_and_frees_the_slot():
    """A request whose deadline lapses MID-DECODE is evicted with its
    partial tokens and a terminal status, and the freed slot admits the
    next waiter the same step — deadlines are how a stuck pool sheds
    load."""
    _, eng = _make_engine(n_slots=1)
    # needs prompt prefill + 8 decode steps but only has budget for ~4
    doomed = Request(0, np.array([1, 2, 3]), 8, arrival=0.0, deadline=4)
    follow = Request(1, np.array([4, 5]), 3, arrival=1.0)
    results, stats = eng.run([doomed, follow])
    assert results[0]["status"] == "DEADLINE_EXPIRED"
    assert 0 < results[0]["tokens"].size < 8, results[0]["tokens"]
    assert results[0]["finish_step"] <= doomed.arrival_step + 4 + 1
    assert results[1]["status"] == "OK"
    assert stats["expired"] == 1 and stats["finished"] == 1
    # the partial stream is a PREFIX of the solo stream (row-
    # independent math: the eviction changed nothing it emitted)
    solo, _ = eng.run_solo(Request(0, np.array([1, 2, 3]), 8,
                                   arrival=0.0))
    np.testing.assert_array_equal(
        results[0]["tokens"], solo[:results[0]["tokens"].size])
    # ... and the follower matches ITS solo run exactly
    solo1, _ = eng.run_solo(follow)
    np.testing.assert_array_equal(results[1]["tokens"], solo1)
