#!/usr/bin/env python3
"""Run ONE serve cell once through benchmark/run.py's own main(), on a
registry and a workload file changed IN MEMORY: no file is rewritten.

    python3 benchmark/tools/serve_probe.py --workload gpt2_345m_serve_steady \
        --seed 11 --seconds 20 --set traffic.arrivals.rate_rps=9.0 \
        --set engine.n_slots=24 --control --out chiprun_out/probe.jsonl

The serve cells are not in BENCHMARK.json (PERF.md section 7): the registry
run.py reads is BENCHMARK.json with benchmark/proposed/serve.json merged in.
`--set dotted.key=json` lays a value over the workload file (under
--rehearse over its rehearsal sizes).  What the sweeps behind a cell's
sizes, its rate and its limits are made with:

  --control      beside the reference, read the control on the same prompts
                 and tokens (the reference in bfloat16 throughout, put in the
                 program's place) and the reference at the platform's
                 default matmul precision: detail.probe
  --fault NAME   plant a fault beneath the timed path (FAULTS below); the
                 run must come out not correct

One JSON line goes to --out: the overrides, the seed, the exit code, the
result line and the loop's detail.  One process a run (a chip belongs to
one process): loop over seeds and points in the shell.
"""

import argparse
import contextlib
import io
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


# --------------------------------------------------------------------------
# the faults a serve cell can have, each planted beneath the timed path
# --------------------------------------------------------------------------
class _Feeds:
    """An Executor whose runs of the step program get their feed changed."""

    def __init__(self, exe, change):
        self._exe, self._change = exe, change

    def __getattr__(self, name):
        return getattr(self._exe, name)

    def run(self, program=None, feed=None, **kw):
        if feed is not None and "pos_mat" in feed:
            feed = self._change(dict(feed))
        return self._exe.run(program, feed=feed, **kw)


def _positions_off_by_one(feed):
    """Every column's position embedding is the next position's."""
    feed["pos_mat"] = feed["pos_mat"] + 1
    return feed


def _chunk_one_row_late(feed):
    """A prefill chunk (more than one column) is written one cache row
    after its place."""
    late = (feed["width_rows"] > 1).astype(feed["pos_rows"].dtype)
    feed["pos_rows"] = feed["pos_rows"] + late
    return feed


def _alter_greedy_tokens(engine_class):
    """A greedy token altered where the engine picks it; returns the
    original to put back."""
    pick = engine_class._pick_tokens

    def altered(self, rows, slots, draft_rows=None):
        out = pick(self, rows, slots, draft_rows=draft_rows)
        for j, slot in enumerate(slots):
            if self.pool.slots[slot].req.greedy:
                out[j] = (out[j] + 1) % rows.shape[-1]
        return out

    engine_class._pick_tokens = altered
    return pick


# name -> (workload overrides, Executor wrapper or None, patches the engine)
FAULTS = {
    "bf16_cache": ([("engine.cache_dtype", "bfloat16")], None, False),
    "positions": ([], lambda exe: _Feeds(exe, _positions_off_by_one), False),
    "late_chunk": ([], lambda exe: _Feeds(exe, _chunk_one_row_late), False),
    "token": ([], None, True),
}


# --------------------------------------------------------------------------
def lay(data, dotted, value):
    keys = dotted.split(".")
    for k in keys[:-1]:
        data = data.setdefault(k, {})
    data[keys[-1]] = value


def merged_registry(load_json):
    """BENCHMARK.json with the proposed serve entries merged in."""
    spec = load_json(ROOT, "BENCHMARK.json")
    more = load_json(BENCH, "proposed", "serve.json")
    train = [c["name"] for c in spec["workloads"]]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in more["train_only"]:
            m["workloads"] = train
    for key in ("workloads", "end_to_end", "per_layer"):
        spec[key] = spec[key] + more[key]
    return spec


def probed(adapter, records):
    """adapter.reference_logits, also reading the control (put in the
    program's place) against the same references, row by row: the nearest,
    as compare() takes it, and each alone."""
    reference = adapter.reference_logits

    def reference_and_control(cfg, work, weights, prompt, tokens):
        import numpy as np

        refs = reference(cfg, work, weights, prompt, tokens)
        serve = sys.modules["benchmark_loops_serve"]
        rows = adapter.control_logits(cfg, work, weights, prompt, tokens)
        each = np.stack([serve.row_errors(rows, ref) for ref in refs])
        records.setdefault("control_bf16", []).extend(each.min(0).tolist())
        for name, errs in zip(adapter.REFERENCES, each):
            records.setdefault("control_bf16_vs_" + name, []).extend(
                errs.tolist())
        return refs

    adapter.reference_logits = reference_and_control


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", default="20")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--fault", choices=sorted(FAULTS), default=None)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, BENCH)
    import run

    over = [(k, json.loads(v)) for k, v in
            (item.split("=", 1) for item in args.set)]
    wrap, patch_engine = None, False
    if args.fault:
        more, wrap, patch_engine = FAULTS[args.fault]
        over += more
    load_json, load_module, records = run.load_json, run.load_module, {}

    def changed_json(*parts):
        if parts[-1] == "BENCHMARK.json":
            return merged_registry(load_json)
        data = load_json(*parts)
        if parts[-1] == args.workload + ".json":
            target = data["rehearse"] if args.rehearse else data
            for key, value in over:
                lay(target, key, value)
        return data

    def changed_module(subdir, name):
        mod = load_module(subdir, name)
        if subdir == "adapters" and args.control and hasattr(
                mod, "control_logits"):
            probed(mod, records)
        if subdir == "loops" and wrap is not None:
            loop = mod.run

            def faulty(ctx):
                ctx["wrap_exe"] = wrap
                return loop(ctx)

            mod.run = faulty
        return mod

    run.load_json = changed_json
    run.load_module = changed_module
    if patch_engine:
        sys.path.insert(0, ROOT)
        from paddle_tpu.serving import ServingEngine

        _alter_greedy_tokens(ServingEngine)

    cmd = ["--workload", args.workload, "--seed", str(args.seed),
           "--seconds", args.seconds, "--trace", args.trace]
    if args.rehearse:
        cmd.append("--rehearse")
    out = io.StringIO()

    class Tee(io.TextIOBase):
        def write(self, text):
            out.write(text)
            return sys.__stdout__.write(text)

        def flush(self):
            sys.__stdout__.flush()

    with contextlib.redirect_stdout(Tee()):
        try:
            rc = run.main(cmd)
        except SystemExit as e:
            rc = e.code if isinstance(e.code, int) else 1
            print("probe: run.main exited: %r" % (e.code,), file=sys.stderr)
    lines = out.getvalue().strip().splitlines()
    detail = next((json.loads(ln[len("detail: "):]) for ln in reversed(lines)
                   if ln.startswith("detail: ")), None)
    last = lines[-1] if lines and lines[-1].startswith("{") else None
    if detail:
        detail.pop("memory_stats", None)
        detail["probe"] = {
            k: {"rows": len(v), "mean": sum(v) / len(v), "max": max(v),
                "min": min(v)} for k, v in records.items() if v}
    rec = {"workload": args.workload, "set": dict(over), "fault": args.fault,
           "seed": args.seed, "rc": rc,
           "line": json.loads(last) if last else None, "detail": detail}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
        f.write(json.dumps(rec) + "\n")
    d, m = detail or {}, (detail or {}).get("metrics", {})
    ref = d.get("reference") or {}
    print("probe: %s seed %d fault %s rc %s correct %s tok/s %s ttft90 %s "
          "itl90 %s attain %s backlog %s/%s occ %s step_ms %s err mean %s "
          "max %s off %s probe %s setup %s failed %s/%s peak_gib %s"
          % (dict(over), args.seed, args.fault, rc,
             (rec["line"] or {}).get("correct"), m.get("serve_tokens_per_s"),
             m.get("ttft_ms_p90"), m.get("itl_ms_p90"), d.get("attainment"),
             d.get("backlog_mid"), d.get("backlog_end"), d.get("occupancy"),
             (d.get("loop_step_ms") or {}).get("50"),
             ref.get("logit_err_mean"), ref.get("logit_err_max"),
             ref.get("off_argmax"), json.dumps(d.get("probe")),
             m.get("setup_s"), d.get("failed"), d.get("attempted"),
             d.get("peak_hbm_gib")), file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
