"""Does the comparison that decides `correct` in kanana2_30b_a3b_train
catch a wrong model, and the stated precision's neighbour below?  Trains
the cell's program as the benchmark does (the same adapter, batches and
seeds) and, after each of `--steps` steps (98 is about what a 20 s window
reaches after its 8 warm-up steps, 110 what a traced run's 12 further
steps do), makes the harness's own comparison (`abs(program loss -
adapter.reference_loss(...)) <= adapter.TOLERANCE`, inside the scope the
forward-only program ran in, so the adapter pairs the program's rows with
the reference's and answers NaN where a paired reading is over its limit)
against the adapter's plain reference on the sampled row with the same
weights: exactly, with each of its deliberate errors (adapter.DEPARTURES:
rotary over all 192, the scale 128^-0.5, kv_a_layernorm left out, the
rotate-half pairing on the published weights, routed_scaling_factor left
out, the shared expert left out, the bias in the weights as well as the
selection), and exactly but with everything in bfloat16.  The exact one
has to pass and every other to fail: `ok` says whether they did.  Run on
a TPU:

    python3 tools/kanana2_departures.py --seed 7 [--steps 98,110]

(`--rehearse` runs the cell's rehearsal sizes on the CPU.)  `--cell
trinity_mini_train` makes the same comparison for another cell whose
adapter has `DEPARTURES`, `compare` and `bf16_unit` (PR 40: the window
left out or off by one, rotary on the wrong kind of layer, the gate, the
per-head norms, the norms on a branch's output, route_scale, route_norm
and the embedding's scale; `--steps 120,132` is what its 32 warm-up
steps and a 20 s window reach); `--cell kimi_linear_48b_a3b_train` (PR
45: the decay a head's mean, left out, beta left out, the k k^T correction
left out, q and k not L2-normalised, the convolution one step ahead, the
output gate left out, rotary on the latent layer, routed_scaling_factor
left out; its reference runs Kimi Delta Attention token by token on the
host, about a minute a reference at 6,144 tokens; `--departures-at 120`
runs the nine wrong ones at that step count alone); `--cell
qwen3_next_80b_a3b_train` (PR 48: the decay left out, beta left out, the
k k^T correction left out, q and k not L2-normalised, value head j reading
key head j mod 16, the convolution one step ahead, the GDN gate a sigmoid,
the gains w in place of 1 + w, rotary over all 256 lanes, the attention's
output gate left out, the shared expert's gate left out, the top-10
weights not renormalised; its reference runs Gated DeltaNet token by token
on the host, about a minute a reference at 8,192 tokens); `--workload
joyai_flash_48b_a3b_train` (PR 61; `--workload` is `--cell`: the module
left out of the loss, reading the normed trunk state, scored against
targets that are not shifted, its combine's halves swapped, lambda 1.0, the
query latent's norm left out, and kanana-2's kv_a_layernorm, routed scale
and shared expert left out; `--steps 130,142`).

Prints one JSON line a step count (the adapter's own lines, with every
reading, go to stderr).  PERF.md (PR 37) keeps what it read; what the
comparison does not catch is pinned on the CPU
(tests/test_kanana2_model.py).
"""

import argparse
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "kanana2_30b_a3b_train"


def _run_py():
    """benchmark/run.py as a module: the registry is read as it reads it."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(ROOT, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", "--workload", dest="cell", default=CELL)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--steps", default="98,110")
    ap.add_argument("--departures-at", default=None,
                    help="step counts (of --steps) at which the wrong "
                         "references run too; the others read the exact "
                         "and the all-bfloat16 one alone (default: all)")
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's rehearsal sizes on the CPU: proves the "
                         "plumbing, its readings mean nothing")
    args = ap.parse_args()

    import jax
    import numpy as np

    import paddle_tpu as fluid

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("kanana2_departures: needs a TPU, jax found %s"
                         % jax.devices())
    run = _run_py()
    spec = run.load_json(ROOT, "BENCHMARK.json")
    cell = run.find(spec["workloads"], args.cell, "workload")
    cfg = run.merged(run.load_json(ROOT, run.find(
        spec["configs"], cell["config"], "config")["file"]), args.rehearse)
    work = run.merged(run.load_json(
        run.BENCH_DIR, "workloads", args.cell + ".json"), args.rehearse)
    adapter = run.load_module("adapters", cfg["adapter"])

    built = adapter.build(cfg, work)
    built["startup"].random_seed = built["main"].random_seed = args.seed + 1
    fwd = adapter.build(cfg, work, forward_only=True)
    ring = [adapter.make_batch(cfg, work, args.seed * 1000 + i)
            for i in range(int(work["ring"]))]
    sample = {k: v[:int(work["reference_rows"])] for k, v in ring[0].items()}
    place = fluid.CPUPlace() if args.rehearse else fluid.TPUPlace(0)
    exe, scope = fluid.Executor(place), fluid.Scope()
    wrong_at = (None if args.departures_at is None
                else [int(n) for n in args.departures_at.split(",") if n])
    ok, done = True, 0
    with fluid.scope_guard(scope):
        exe.run(built["startup"])
        for steps in [int(n) for n in args.steps.split(",")]:
            for i in range(done, steps):
                out = exe.run(built["main"], feed=ring[i % len(ring)],
                              fetch_list=[built["loss"]], return_numpy=False)
            done = steps
            result = {"seed": args.seed, "steps": steps,
                      "train_loss": float(np.asarray(out[0]).reshape(-1)[0]),
                      "tolerance": adapter.TOLERANCE,
                      "limits": adapter.LIMITS, "abs_diff": {},
                      "readings": {}, "passes": {}}
            got = float(np.asarray(exe.run(
                fwd["main"], feed=sample,
                fetch_list=[fwd["loss"]])[0]).reshape(-1)[0])
            result["program_loss"] = got
            params = [(p.name, scope.find_var(p.name))
                      for p in fwd["main"].global_block().all_parameters()]
            unit = adapter.bf16_unit(cfg, params, sample)
            wrong = (adapter.DEPARTURES
                     if wrong_at is None or steps in wrong_at else ())
            for dtype, departure in (
                    [("float32", d) for d in (None,) + wrong]
                    + [("bfloat16", None)]):
                name = departure or ("exact" if dtype == "float32"
                                     else "all_" + dtype)
                # what `reference_loss` hands the harness, with the
                # readings behind it
                told, loss, found = adapter.compare(
                    cfg, params, sample, departure, dtype, unit)
                result["abs_diff"][name] = abs(got - loss)
                result["readings"][name] = found
                result["passes"][name] = bool(
                    abs(got - told) <= adapter.TOLERANCE)
            result["ok"] = all(v == (k == "exact")
                               for k, v in result["passes"].items())
            ok = ok and result["ok"]
            print(json.dumps(result), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
