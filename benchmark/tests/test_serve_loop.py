"""loops/serve.py on a fake clock: no test sleeps and none compares a
duration.  The generator (same work for every seed), the open loop on a
stub engine (due times, lateness, the window's edges, the drain's end), the
arithmetic on hand-made records, the proposed registry entries, and
`correct`: the control and every fault a serve cell can have must each
read false.

The serve cells are NOT in BENCHMARK.json (PERF.md section 7): the tests
run them on the registry tools/serve_probe.py merges in memory."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH_DIR, ROOT, RUN, SPEC

SERVE = RUN.load_module("loops", "serve")
PROBE = RUN.load_module("tools", "serve_probe")
MERGED = PROBE.merged_registry(RUN.load_json)
PROPOSED = RUN.load_json(BENCH_DIR, "proposed", "serve.json")
SERVE_CELLS = [c["name"] for c in PROPOSED["workloads"]]
TRAIN_CELLS = [c["name"] for c in SPEC["workloads"]]


def load_cell(cell_name, rehearse=True):
    """(cfg, work, adapter) of a proposed cell, as conftest.load_cell gives
    a registered one."""
    cell = RUN.find(MERGED["workloads"], cell_name, "workload")
    entry = RUN.find(MERGED["configs"], cell["config"], "config")
    cfg = RUN.merged(RUN.load_json(ROOT, entry["file"]), rehearse)
    work = RUN.merged(RUN.load_json(
        BENCH_DIR, "workloads", cell_name + ".json"), rehearse)
    return cfg, work, RUN.load_module("adapters", cfg["adapter"])


def _traffic(cell):
    return RUN.load_json(BENCH_DIR, "workloads", cell + ".json")["traffic"]


# --------------------------------------------------------------------------
# the generator
# --------------------------------------------------------------------------
@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_every_seed_offers_the_same_work_in_another_order(cell):
    traffic = _traffic(cell)
    assert "pattern_seed" not in traffic and traffic["source"]
    segments = [("ramp", 0.0, 5.0), ("window", 5.0, 20.0)]
    a = SERVE.make_schedule(traffic, 50257, 7, segments)
    b = SERVE.make_schedule(traffic, 50257, 2 ** 31 + 11, segments)

    def multiset(sched, key):
        return sorted(key(r) for r in sched if r["phase"] == "window")

    for key in (lambda r: r["prompt"].size, lambda r: r["max_new_tokens"],
                lambda r: (r["temperature"], r["top_k"], r["top_p"],
                           r["seed"] is None)):
        assert multiset(a, key) == multiset(b, key)
    assert [r["prompt"].size for r in a] != [r["prompt"].size for r in b]
    assert [r["due"] for r in a] != [r["due"] for r in b]
    sampled = [(x["seed"], y["seed"]) for x, y in zip(a, b)
               if x["seed"] is not None and y["seed"] is not None]
    assert sampled and all(x != y for x, y in sampled)
    n = round(traffic["arrivals"]["rate_rps"] * 20.0)
    assert len(multiset(a, lambda r: r["due"])) == n
    t_max = RUN.load_json(BENCH_DIR, "workloads", cell + ".json")[
        "engine"]["t_max"]
    for sched in (a, b):
        assert [r["rid"] for r in sched] == list(range(len(sched)))
        assert all(x["due"] <= y["due"] for x, y in zip(sched, sched[1:]))
        for r in sched:
            lo, hi = (0.0, 5.0) if r["phase"] == "ramp" else (5.0, 25.0)
            assert lo <= r["due"] < hi
            assert r["prompt"].size + r["max_new_tokens"] <= t_max + 1
            assert r["prompt"].min() >= 1 and r["prompt"].max() < 50257
    greedy = sum(r["seed"] is None for r in a if r["phase"] == "window")
    assert abs(greedy - n / 2) <= 1
    same = SERVE.make_schedule(traffic, 50257, 7, segments)
    assert all((x["prompt"] == y["prompt"]).all() and x["due"] == y["due"]
               for x, y in zip(a, same))


@pytest.mark.parametrize("n, stratum", [(60, 6), (112, 6), (7, 6), (5, 2)])
def test_every_run_of_a_stratum_holds_one_value_of_each_band(n, stratum):
    rng = np.random.default_rng(3)
    values = list(range(n))
    dealt = SERVE._dealt(values, rng, stratum)
    assert sorted(dealt) == values and dealt != values
    runs = -(-n // stratum)
    at = 0
    while at < n:
        run = [x for x in dealt[at:at + stratum]]
        bands = [x // runs for x in run]
        # a run holds no band twice (the last bands may be short)
        assert len(set(bands)) == len(bands) or n % stratum, (run, bands)
        at += stratum
    full = [dealt[i:i + stratum] for i in range(0, n - n % stratum, stratum)]
    if n % stratum == 0:
        assert all(sorted(x // runs for x in run) == list(range(stratum))
                   for run in full)
    other = SERVE._dealt(values, np.random.default_rng(4), stratum)
    assert other != dealt and sorted(other) == values


# --------------------------------------------------------------------------
# the open loop, on a stub engine and a fake clock
# --------------------------------------------------------------------------
class _Req:
    def __init__(self, rid, prompt, max_new_tokens):
        self.rid, self.prompt = rid, np.asarray(prompt)
        self.max_new_tokens = max_new_tokens


class _Slot:
    def __init__(self, req):
        self.req, self.state, self.pos, self.out = req, "prefill", 0, []


class _Pool:
    def __init__(self, n):
        self.slots = [None] * n

    def active_slots(self):
        return [(i, s) for i, s in enumerate(self.slots) if s is not None]

    def evict(self, slot):
        self.slots[slot] = None


class StubEngine:
    """ServingEngine's scheduling as the loop sees it: FIFO admission into
    free slots, a prompt in chunks of `width`, then a token a step."""

    def __init__(self, n_slots=2, width=4, stall_rids=()):
        self.pool, self.width, self.now = _Pool(n_slots), width, 0
        self.queue, self._results = [], {}
        self.stall = set(stall_rids)   # requests that never finish

    def submit(self, req):
        self.queue.append(req)

    def wire_results(self, rids):
        return [dict(self._results[r], rid=r) for r in rids]

    def step(self):
        for i, s in enumerate(self.pool.slots):
            if s is None and self.queue:
                self.pool.slots[i] = _Slot(self.queue.pop(0))
        done = []
        for i, s in self.pool.active_slots():
            p = s.req.prompt.size
            if s.state == "prefill":
                s.pos = min(p, s.pos + self.width)
                if s.pos < p:
                    continue
                s.state = "decode"
            else:
                s.pos += 1
            if s.req.rid in self.stall and len(s.out) + 1 >= s.req.max_new_tokens:
                s.pos -= 1
                continue
            s.out.append(7)
            if len(s.out) >= s.req.max_new_tokens:
                self._results[s.req.rid] = {
                    "tokens": np.asarray(s.out), "status": "OK",
                    "admit_step": 0}
                self.pool.slots[i] = None
                done.append(s.req.rid)
        self.now += 1
        return done


def _request(rid, due, p=6, new=3, phase="window"):
    return {"rid": rid, "due": due, "phase": phase,
            "prompt": np.arange(1, p + 1), "max_new_tokens": new,
            "temperature": 1.0, "top_k": 0, "top_p": 1.0, "seed": None}


def _drive(schedule, t_end, step_s=0.1, **engine):
    clock = SERVE.FakeClock(step_s)
    eng = StubEngine(**engine)
    reqs, steps = SERVE.drive(
        eng, schedule, clock, clock.sleep, t_end,
        lambda r, now: _Req(r["rid"], r["prompt"], r["max_new_tokens"]),
        step_done=clock.step_done)
    return reqs, steps, eng


def test_requests_are_submitted_at_their_due_times_and_timed_from_them():
    # 6-token prompts at width 4: two prefill steps (the second emits the
    # first token), then a token a step; a step is 0.1 s
    sched = [_request(0, 0.0), _request(1, 0.25), _request(2, 1.0)]
    reqs, steps, _ = _drive(sched, 10.0)
    # rid 0: due 0, submitted at 0, tokens after steps 2, 3, 4
    assert reqs[0]["submitted"] == 0.0
    assert reqs[0]["emitted"] == pytest.approx([0.2, 0.3, 0.4])
    # rid 1 is due inside a step: submitted when that step returns (0.3),
    # 0.05 s late, and its first token is timed from 0.25
    assert reqs[1]["submitted"] == pytest.approx(0.3)
    assert reqs[1]["emitted"][0] == pytest.approx(0.5)
    # rid 2 finds the engine idle: the loop slept to its due time
    assert reqs[2]["submitted"] == pytest.approx(1.0)
    assert reqs[2]["admitted"] == pytest.approx(1.0)
    s = SERVE.summarize(reqs, steps, 0.0, 5.0,
                        {"ttft_ms": 240.0, "itl_ms": 150.0}, 2, 16)
    assert s["attempted"] == 3 and s["failed"] == 0
    assert sorted(s["lateness_ms"]) == pytest.approx([0.0, 0.0, 50.0])
    assert sorted(s["ttft_ms"]) == pytest.approx([200.0, 200.0, 250.0])
    assert s["itl_ms"] == pytest.approx([100.0] * 3)
    # rid 1's first token came 250 ms after it was due: over the limit
    assert s["attainment"] == pytest.approx(100.0 * 2 / 3)
    assert all(r["status"] == "OK" for r in reqs.values())


def test_a_third_request_waits_for_a_slot_and_the_wait_is_counted():
    sched = [_request(i, 0.0) for i in range(3)]
    reqs, steps, _ = _drive(sched, 10.0, n_slots=2)
    assert reqs[0]["admitted"] == reqs[1]["admitted"] == 0.0
    # the first two finish in the fourth step; the third is admitted by the fifth
    assert reqs[2]["admitted"] == pytest.approx(0.4)
    s = SERVE.summarize(reqs, steps, 0.0, 5.0,
                        {"ttft_ms": 1e9, "itl_ms": 1e9}, 2, 16)
    assert sorted(s["queue_wait_ms"]) == pytest.approx([0.0, 0.0, 400.0])
    assert steps[0]["queued"] == 1 and steps[0]["active"] == 2


def test_only_what_ends_in_the_window_counts():
    # due in the ramp, in the window, and in the window but finishing in
    # the drain: tokens count by WHEN they were emitted, requests by when
    # they were DUE
    sched = [_request(0, 0.0, phase="ramp"), _request(1, 1.0),
             _request(2, 1.8, new=5)]
    reqs, steps, _ = _drive(sched, 10.0)
    s = SERVE.summarize(reqs, steps, 1.0, 2.0,
                        {"ttft_ms": 1e9, "itl_ms": 1e9}, 2, 16)
    assert s["attempted"] == 2 and s["failed"] == 0
    # rid 1: tokens at 1.2, 1.3, 1.4; rid 2 (due 1.8): token at 2.0 is
    # OUT (the window is half open), so are 2.1 .. 2.4
    assert s["tokens_in_window"] == 3
    assert s["steps_in_window"] == len([x for x in steps
                                        if 1.0 <= x["t1"] < 2.0])
    assert s["backlog_end"] == 1 and s["backlog_mid"] == 0
    # the work of the window's steps: rid 1's 6 prompt columns and 2 decode
    # columns, rid 2's first chunk of 4 (its step ended at 1.9)
    assert s["work"]["columns"] == 6 + 2 + 4
    assert s["work"]["sampled"] == 3


def test_a_request_unfinished_at_the_drains_end_is_failed_and_misses_both():
    sched = [_request(0, 0.0), _request(1, 0.0)]
    reqs, steps, eng = _drive(sched, 3.0, stall_rids={1})
    assert reqs[1]["status"] is None and len(reqs[1]["emitted"]) == 2
    assert len(eng.pool.active_slots()) == 1      # still there at t_end
    s = SERVE.summarize(reqs, steps, 0.0, 1.0,
                        {"ttft_ms": 1e9, "itl_ms": 1e9}, 2, 16)
    assert s["attempted"] == 2 and s["failed"] == 1
    assert s["attainment"] == 50.0
    assert len(s["ttft_ms"]) == 1                 # a failed request is no sample
    assert steps[-1]["t0"] < 3.0 <= steps[-1]["t1"] + 1e-9
    SERVE.abandon(eng)
    assert not eng.pool.active_slots() and not eng.queue


def test_the_step_records_count_columns_keys_and_rows():
    reqs, steps, _ = _drive([_request(0, 0.0, p=6, new=3)], 5.0)
    # steps: chunk 0..3 | chunk 4..5 (+ first token) | decode at 6 | at 7
    assert [(x["prefill_cols"], x["decode_cols"]) for x in steps] == [
        (4, 0), (2, 0), (0, 1), (0, 1)]
    # keys each column may see: 1+2+3+4 | 5+6 | 7 | 8
    assert [x["context_sum"] for x in steps] == [10, 11, 7, 8]
    assert [x["rows_read"] for x in steps] == [4, 6, 7, 8]
    assert [x["sampled"] for x in steps] == [0, 1, 1, 1]
    assert [x["live_rows"] for x in steps] == [4, 6, 7, 0]   # evicted at the end


def test_on_time_callbacks_run_once_before_the_first_step_at_or_after():
    seen = []
    clock = SERVE.FakeClock(0.1)
    eng = StubEngine()
    SERVE.drive(eng, [_request(0, 0.0, new=8)], clock, clock.sleep, 5.0,
                lambda r, now: _Req(r["rid"], r["prompt"], r["max_new_tokens"]),
                step_done=clock.step_done,
                on_time=[(0.35, lambda: seen.append(("a", round(clock(), 6)))),
                         (0.0, lambda: seen.append(("b", round(clock(), 6))))])
    assert seen == [("b", 0.0), ("a", 0.4)]


def test_the_loop_takes_its_clock_and_sleep_from_the_caller():
    """No wall clock in drive(): a clock that never moves by itself serves
    everything (time passes only through sleep and step_done)."""
    reqs, _, _ = _drive([_request(0, 100.0), _request(1, 200.0)], 1e6)
    assert reqs[1]["emitted"][-1] == pytest.approx(200.4)


# --------------------------------------------------------------------------
# arithmetic
# --------------------------------------------------------------------------
@pytest.mark.parametrize("values, q, want", [
    ([], 90, None), ([5.0], 90, 5.0), ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4, 5], 90, 4.6), (list(range(101)), 90, 90.0),
    ([10.0, 0.0], 25, 2.5),
])
def test_percentile(values, q, want):
    got = SERVE.percentile(values, q)
    assert got == (None if want is None else pytest.approx(want))


def test_serve_flops_and_bytes_are_the_closed_forms():
    cfg, work, _ = load_cell("gpt2_345m_serve_steady", rehearse=False)
    adapter = RUN.load_module("adapters", work["adapter"])
    d, n, v = 1024, 24, 50257
    assert adapter.matmul_params(cfg["model"]) == n * 12 * d * d
    # 10 columns seeing 55 keys between them, 3 rows sampled
    assert adapter.serve_flops(cfg, 10, 55, 3) == (
        2.0 * n * 12 * d * d * 10 + 4.0 * d * n * 55 + 2.0 * d * v * 3)
    row = n * 2 * d * np.dtype(work["engine"]["cache_dtype"]).itemsize
    assert adapter.cache_row_bytes(cfg, work) == row
    assert adapter.serve_step_bytes(cfg, work, 2, 100, 10) == (
        2 * 4.0 * (n * 12 * d * d + v * d) + row * 110)
    # serve_mfu of a hand-made window: 1e15 operations in 20 s on one chip
    peak = RUN.load_json(BENCH_DIR, "peaks.json")["TPU v5 lite"]
    assert 100.0 * 1e15 / 20.0 / peak["flops_per_s"] == pytest.approx(25.380710659)


def test_the_roofline_reader_scales_the_work_to_the_steps_the_trace_holds():
    """Why serve_step_hbm_roofline cannot read over 100: the bytes are the
    least the steps can move and the time is the device's busy time in the
    window; the window drops the first run, so the work is scaled to the
    runs it holds and not credited for a step the time leaves out."""
    cfg, work, _ = load_cell("gpt2_345m_serve_steady", rehearse=False)
    adapter = RUN.load_module("adapters", work["adapter"])
    reader = RUN.load_module("readers", "serve_roofline")
    peak = RUN.load_json(BENCH_DIR, "peaks.json")["TPU v5 lite"]
    slice_work = {"steps": 20, "columns": 600, "context_sum": 10 ** 5,
                  "sampled": 500, "rows_read": 200000}
    need = adapter.serve_step_bytes(cfg, work, 20, 200000, 600)
    ctx = {"trace": {"steps": 19, "busy_s": 19 * 0.020, "window_s": 1.9},
           "serve_slice_work": slice_work, "serve_adapter": adapter,
           "peak": peak, "cfg": cfg, "work": work, "chips": 1}
    got = reader.read(ctx, "hbm_roofline")
    assert got == pytest.approx(100.0 * (19 / 20) * need / 819e9 / (19 * 0.020))
    assert 0 < got < 100
    mfu = reader.read(ctx, "step_mfu")
    assert mfu == pytest.approx(100.0 * 0.95 * adapter.serve_flops(
        cfg, 600, 10 ** 5, 500) / 197e12 / 1.9)
    assert reader.read(dict(ctx, trace=None), "hbm_roofline") is None
    assert reader.read(dict(ctx, serve_slice_work=None), "step_mfu") is None


def test_the_span_reader_shares_an_iterations_time_among_the_engines_spans():
    reader = RUN.load_module("readers", "serve_span")
    it, run, samp, adm = (reader.ITER, reader.SPANS["run_share"],
                          reader.SPANS["sample_share"],
                          reader.SPANS["admit_share"])
    events = [(0, 100, it), (0, 10, adm), (10, 70, run), (70, 95, samp),
              (200, 300, it), (200, 205, adm), (205, 265, run),
              (265, 300, samp),
              (400, 450, run)]          # under no iteration: not counted
    out = reader.reduce_events(events)
    assert out["iterations"] == 2 and out["iter_ms"] == 100
    assert out["run_share"] == pytest.approx(60.0)
    assert out["sample_share"] == pytest.approx(30.0)
    assert out["admit_share"] == pytest.approx(7.5)
    assert out["other_share"] == pytest.approx(2.5)
    assert reader.reduce_events([(0, 1, run)]) is None
    assert reader.read({}, "run_share") is None


# --------------------------------------------------------------------------
# the registry: what is there stays, what is proposed is sound
# --------------------------------------------------------------------------
def test_the_serve_cells_are_proposed_and_not_registered():
    """tests/test_kernel_tuning.py (tier-1) pins two train metrics' lists to
    every cell of BENCHMARK.json but three, so a cell that reports no
    train_mfu cannot be registered soundly until that pin is changed."""
    assert not set(SERVE_CELLS) & set(TRAIN_CELLS)
    assert not [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]
                if m["name"].startswith(SERVE_ONLY)]
    for cell in SERVE_CELLS:
        assert os.path.isfile(os.path.join(BENCH_DIR, "workloads",
                                           cell + ".json"))


def test_the_merged_registry_keeps_the_contracts_rules():
    cells = {c["name"] for c in MERGED["workloads"]}
    names = [m["name"] for m in MERGED["end_to_end"] + MERGED["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in MERGED["end_to_end"]}
    for m in MERGED["end_to_end"] + MERGED["per_layer"]:
        assert set(m.get("workloads", [])) <= cells, m["name"]
    for m in MERGED["per_layer"]:
        moved = e2e[m["moves"]]
        if m["name"] in PINNED_TO_EVERY_CELL:
            continue   # their lists are tests/test_kernel_tuning.py's
        for cell in m.get("workloads", cells):
            if "workloads" in moved and cell not in moved["workloads"]:
                assert "workloads" not in m, m["name"]
    for m in PROPOSED["per_layer"]:
        how = RUN.load_json(BENCH_DIR, "layer_metrics", m["name"] + ".json")
        assert hasattr(RUN.load_module("readers", how["reader"]), "read")
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]]["workloads"], (
                "%s lists %s, which does not report %s"
                % (m["name"], cell, m["moves"]))


# the two train metrics that the tier-1 pin asks every new cell to join
PINNED_TO_EVERY_CELL = ("attention_pairs_computed_over_visible",
                        "attention_block_fetches_over_tiles")


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_a_serve_cell_is_asked_for_no_train_metric(cell):
    names = {m["name"] for m in RUN.cell_metrics(MERGED["end_to_end"], cell)}
    assert names == {"serve_tokens_per_s", "serve_mfu", "ttft_ms_p90",
                     "itl_ms_p90", "setup_s"}
    layer = {m["name"] for m in RUN.cell_metrics(MERGED["per_layer"], cell)}
    assert not {"run_call_ms", "stall_share"} & layer
    assert not [n for n in layer if n.startswith("train_")]


SERVE_ONLY = ("serve_", "kv_", "ttft_", "itl_", "generator_")


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_a_train_cell_reports_the_same_with_the_serve_entries_merged(cell):
    for key in ("end_to_end", "per_layer"):
        assert ([m["name"] for m in RUN.cell_metrics(MERGED[key], cell)]
                == [m["name"] for m in RUN.cell_metrics(SPEC[key], cell)])


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_a_proposed_cell_rehearses_traced_to_its_end(cell, tmp_path):
    """The whole command on the merged registry: run.py's own main(), the
    traced slice and every reader, at the rehearsal's sizes."""
    out = tmp_path / "probe.jsonl"
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "tools", "serve_probe.py"),
         "--workload", cell, "--seed", "3", "--trace", "1", "--rehearse",
         "--out", str(out)],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    assert "REHEARSAL" in p.stdout
    assert p.stderr.strip().splitlines()[-2].startswith(
        "compared (value, limit): ")
    tag = "rehearsal line (NOT a result): "
    line, = [json.loads(ln[len(tag):]) for ln in p.stdout.splitlines()
             if ln.startswith(tag)]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    want = {m["name"] for m in RUN.cell_metrics(MERGED["per_layer"], cell)
            if m["source"] != "device_trace"}
    # the CPU reports no memory; of the metrics that move train_mfu (which
    # no serve cell reports) those without a list that find something to
    # read in a step program are printed, the training profile's are not
    got = set(line["metrics"])
    train = {m["name"] for m in MERGED["per_layer"]
             if m["moves"] == "train_mfu"}
    assert got - train == want - train - {"peak_hbm_gib"}
    assert got & train == {"compiles_in_window", "host_feed_ms", "fused_ops",
                           "mosaic_calls", "rng_generator_ops",
                           "state_relayouts"}
    rec = json.loads(out.read_text())
    assert rec["detail"]["reference"]["ok"]


# --------------------------------------------------------------------------
# `correct`: the control and the faults, at a size a test can hold
# --------------------------------------------------------------------------
# as deep as the model served: what bfloat16 between the matmuls adds grows
# with the depth (0.008 at 4 layers, 0.013 at 24, the chip's reading at the
# cell's own size), and the limits are the chip's
TINY = {"model": {"vocab_size": 300, "n_ctx": 64, "d_model": 64,
                  "n_layer": 24, "n_head": 2, "tie_embeddings": True}}
TINY_WORK = {"engine": {"t_max": 64, "cache_dtype": "float32"},
             "traffic": {"output_len": {"hi": 32}}}


def _adapter():
    return RUN.load_module("adapters", "gpt2_serve")


@pytest.mark.parametrize("seed", [1, 3, 6])
def test_the_control_in_the_programs_place_is_not_correct(seed):
    """The reference in bfloat16 throughout, put where the program's rows
    go, reads over the adapter's limits; the reference's own rows read 0."""
    adapter = _adapter()
    weights = adapter.make_weights(TINY, seed)
    rng = np.random.default_rng(seed)
    own, control = [], []
    for i in range(4):
        prompt, tokens = rng.integers(1, 300, 20), rng.integers(1, 300, 32)
        refs = adapter.reference_logits(TINY, TINY_WORK, weights, prompt,
                                        tokens)
        assert len(refs) == len(adapter.REFERENCES) == 2
        assert refs[0].shape == (32, 300) and refs[0].dtype == np.float32
        served = refs[0].argmax(-1)
        own.append((i, refs[0], refs, served))
        control.append((i, adapter.control_logits(
            TINY, TINY_WORK, weights, prompt, tokens), refs, served))
    limits = {k: v for k, v in adapter.SERVE_TOLERANCE.items()
              if k != "off_argmax"}
    sound = SERVE.compare(own, limits)
    assert sound["ok"] and sound["logit_err_max"] == 0.0
    got = SERVE.compare(control, limits)
    assert not got["ok"], got
    assert got["logit_err_mean"] > 1.5 * limits["logit_err_mean"], got
    assert got["logit_err_max"] > 1.5 * limits["logit_err_max"], got


def test_the_reference_is_causal_and_padding_cannot_reach_a_row():
    adapter = _adapter()
    weights = adapter.make_weights(TINY, 2)
    prompt, tokens = np.arange(1, 11), np.arange(20, 26)
    ref = adapter.reference_logits(TINY, TINY_WORK, weights, prompt, tokens)[0]
    longer = adapter.reference_logits(TINY, TINY_WORK, weights, prompt,
                                      np.arange(20, 30))[0]
    assert np.array_equal(ref, longer[:6])   # later tokens change no row
    other = adapter.reference_logits(TINY, TINY_WORK, weights, prompt + 1,
                                     tokens)[0]
    assert not np.allclose(ref, other)
    with pytest.raises(ValueError):
        adapter.reference_logits(TINY, TINY_WORK, weights, np.arange(1, 62),
                                 tokens)


def test_the_watched_are_greedy_requests_with_the_longest_in_it():
    def req(rid, p, n, seed=None):
        return {"rid": rid, "prompt": np.arange(p), "seed": seed,
                "max_new_tokens": n}
    sched = [req(0, 5, 3), req(1, 50, 30), req(2, 90, 40, seed=7),  # sampled
             req(3, 9, 2), req(4, 8, 8), req(5, 7, 7), req(6, 6, 6)]
    got = SERVE.pick_watch(sched, 3, 11)
    assert got[0] == 1 and len(got) == 3 and set(got) <= {0, 1, 3, 4, 5, 6}
    assert got == SERVE.pick_watch(sched, 3, 11)
    assert sorted(SERVE.pick_watch(sched, 64, 11)) == [0, 1, 3, 4, 5, 6]
    assert SERVE.pick_watch([sched[2]], 3, 11) == []


def test_the_tap_keeps_the_row_a_slots_last_real_column_fetched():
    class Exe:
        compile_count = 5

        def run(self, program=None, feed=None, **kw):
            return [np.arange(2 * 4 * 3, dtype="float32").reshape(2, 4, 3)
                    + feed["step"]]

    tap = SERVE.Tap(Exe())
    tap.program, tap.watch = "step", {9}
    assert tap.compile_count == 5            # everything else goes through
    tap.run("reset", feed={"width_rows": np.array([1, 1]), "step": 0})
    assert tap.last is None                  # another program: not kept
    feed = {"width_rows": np.array([1, 3]), "step": 100}
    out = tap.run("step", feed=feed, fetch_list=["logits"])
    tap.keep(9, 1)                           # slot 1, its third column
    tap.keep(9, 0)
    assert np.array_equal(tap.rows[9][0], out[0][1, 2])
    assert np.array_equal(tap.rows[9][1], out[0][0, 0])
    out[0][:] = 0                            # a copy was kept
    assert tap.rows[9][0].sum() > 0


def test_compare_reads_the_rows_distance_and_the_tokens_choice():
    assert not SERVE.compare([], {"logit_err_max": 1.0})["ok"]
    ref = np.array([[1.0, 2.0, 3.0], [0.0, 0.0, 3.0]])
    rows = ref + np.array([[0.0, 0.0, 0.0], [0.1, 0.0, 0.0]])
    got = SERVE.compare([(0, rows, [ref], [2, 2])],
                        {"logit_err_max": 0.05, "off_argmax": 0})
    # row 2's difference 0.1 over the norm of (-1, -1, 2)
    assert got["logit_err_max"] == pytest.approx(0.1 / 6 ** 0.5)
    assert got["logit_err_mean"] == pytest.approx(0.05 / 6 ** 0.5)
    assert got["ok"] and got["tokens"] == 2 and got["off_argmax"] == 0
    assert not SERVE.compare([(0, rows, [ref], [2, 1])],
                             {"logit_err_max": 0.05, "off_argmax": 0})["ok"]
    assert not SERVE.compare([(0, rows, [ref], [2, 2])],
                             {"logit_err_max": 0.04})["ok"]
    # of two references the nearer counts, row by row
    two = SERVE.compare([(0, rows, [ref + 1.0, rows], [2, 2])],
                        {"logit_err_max": 0.0})
    assert two["ok"] and two["logit_err_mean_by_reference"][1] == 0.0
    assert two["logit_err_mean_by_reference"][0] > 0.5
    # a constant added to the reference's row changes no probability and
    # not the scale the distance is taken against
    assert SERVE.row_errors(rows, ref)[1] == pytest.approx(
        SERVE.row_errors(rows + 5.0, ref + 5.0)[1])


def _rehearsal_ctx(cell, seed, wrap_exe=None, **work_over):
    """What run.py hands the loop, without its look for a chip."""
    import argparse

    import jax

    cfg, work, _ = load_cell(cell)
    work = json.loads(json.dumps(work))
    for key, value in work_over.items():
        PROBE.lay(work, key, value)
    ctx = {
        "t_start": 0.0, "seconds": 30.0, "cell": {"name": cell},
        "args": argparse.Namespace(seed=seed, trace=0, keep_trace=None),
        "cfg": cfg, "work": work, "chips": 1, "devices": jax.devices()[:1],
        "rehearse": True, "root": ROOT, "log": lambda msg: None,
        "load_module": RUN.load_module,
        "peak": RUN.load_json(BENCH_DIR, "peaks.json")["rehearsal"],
    }
    if wrap_exe is not None:
        ctx["wrap_exe"] = wrap_exe
    return ctx


@pytest.fixture(scope="module")
def sound_run():
    return SERVE.run(_rehearsal_ctx("gpt2_345m_serve_steady", 5))


def test_the_rest_of_a_run_is_correct(sound_run):
    """The whole of run(ctx) on the CPU at the rehearsal's sizes: the rows
    the Tap kept are the reference's to rounding, and every greedy token is
    the first choice of its row."""
    assert sound_run["correct"] and sound_run["failed"] == 0
    ref = sound_run["detail"]["reference"]
    assert ref["requests"] == 3 and ref["tokens"] >= 6
    assert ref["logit_err_max"] < 1e-5 and ref["off_argmax"] == 0
    assert ref["limits"] == _adapter().SERVE_TOLERANCE


def _broken_run(fault):
    from paddle_tpu.serving import ServingEngine

    over, wrap, patch_engine = PROBE.FAULTS[fault]
    if patch_engine:
        pick = PROBE._alter_greedy_tokens(ServingEngine)
    try:
        return SERVE.run(_rehearsal_ctx(
            "gpt2_345m_serve_steady", 5, wrap_exe=wrap, **dict(over)))
    finally:
        if patch_engine:
            ServingEngine._pick_tokens = pick


@pytest.mark.parametrize("fault", ["late_chunk", "positions", "token"])
def test_a_fault_beneath_the_timed_path_is_not_correct(fault, sound_run):
    """Each fault a serve cell can have, planted under run(ctx): position
    embeddings off by one, a prefill chunk written one cache row late, a
    greedy token altered where the engine picks it.  The run still serves
    every request to its end; the reference alone says it is not correct."""
    broken = _broken_run(fault)
    assert broken["failed"] == 0
    assert broken["attempted"] == sound_run["attempted"]
    assert not broken["correct"]
    ref, sound = broken["detail"]["reference"], sound_run["detail"]["reference"]
    assert not ref["ok"]
    if fault == "token":
        assert ref["off_argmax"] == ref["tokens"] and sound["off_argmax"] == 0
        assert ref["logit_err_max"] < 1e-5   # the rows themselves are sound
    else:
        assert ref["logit_err_mean"] > 10 * ref["limits"]["logit_err_mean"]


def test_a_bfloat16_cache_shows_where_the_matmuls_are_exact(sound_run):
    """A cache kept in bfloat16 under a file that says float32: on the CPU,
    whose float32 matmuls are exact, the rows move 2,000 x their sound
    reading.  On the chip the stated matmul precision rounds K and V to
    bfloat16 as they enter QK^T and PV, so the same cache reads the sound
    run's numbers digit for digit (PERF.md section 2): there it is no loss
    of the stated precision, and no limit set from chip readings can or
    should fail it.  Its 0.0005 here is under those limits."""
    ref = _broken_run("bf16_cache")["detail"]["reference"]
    sound = sound_run["detail"]["reference"]
    assert ref["logit_err_mean"] > 1000 * sound["logit_err_mean"]
    assert ref["off_argmax"] == 0


def test_a_run_that_leaves_requests_unfinished_is_not_correct():
    """A drain too short for what is in flight: `failed` counts them and
    `correct` is false whatever the reference says."""
    out = SERVE.run(_rehearsal_ctx(
        "gpt2_345m_serve_saturated", 9, drain_seconds=0.0, max_steps=40,
        **{"traffic.arrivals.rate_rps": 40.0}))
    assert out["failed"] > 0 and not out["correct"]
    assert out["detail"]["zz_compared"]["failed"] == [out["failed"], 0]
