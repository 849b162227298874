"""Does the comparison that decides `correct` in ouro_2b6_train catch a
wrong model, and the stated precision's neighbour below?  Trains the cell's
program as the benchmark does (the same adapter, batches and seeds) and,
after each of `--steps` steps (70 is what a 20 s window reaches after its 2
warm-up steps, 82 what a traced run's 12 further steps do; by ~100 the gate
has collapsed onto the first loop step), makes the harness's own comparison
(`abs(program loss - adapter.reference_loss(...)) <= adapter.TOLERANCE`,
inside the scope the forward-only program ran in) against the adapter's
plain reference on the sampled row with the same weights: exactly, with
each of its deliberate errors (three loop steps instead of four, the
entropy term left out, the gate reading the state before the final norm,
the last step weighed by its own gate), and exactly but with everything in
bfloat16.  The exact one has to pass and every other to fail; `ok` says
whether they did.  Run on a TPU:

    python3 tools/ouro_departures.py --seed 7 [--steps 70,100] [--save DIR]

Prints one JSON line a step count (the adapter's own lines, with every
reading, go to stderr); `--save` keeps the compared rows as .npz.  PERF.md
(PR 32) keeps what it read.
"""

import argparse
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "ouro_2b6_train"
EXIT_STAT = "ouro_exit_step_mean"


def _run_py():
    """benchmark/run.py as a module: the registry is read as it reads it."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(ROOT, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--steps", default="70,100")
    ap.add_argument("--save", default=None)
    args = ap.parse_args()

    import jax
    import numpy as np

    import paddle_tpu as fluid

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("ouro_departures: needs a TPU, jax found %s"
                         % jax.devices())
    run = _run_py()
    spec = run.load_json(ROOT, "BENCHMARK.json")
    cell = run.find(spec["workloads"], CELL, "workload")
    cfg = run.merged(run.load_json(ROOT, run.find(
        spec["configs"], cell["config"], "config")["file"]), False)
    work = run.merged(run.load_json(
        run.BENCH_DIR, "workloads", CELL + ".json"), False)
    adapter = run.load_module("adapters", cfg["adapter"])

    built = adapter.build(cfg, work)
    built["startup"].random_seed = built["main"].random_seed = args.seed + 1
    fwd = adapter.build(cfg, work, forward_only=True)
    ring = [adapter.make_batch(cfg, work, args.seed * 1000 + i)
            for i in range(int(work["ring"]))]
    sample = {k: v[:int(work["reference_rows"])] for k, v in ring[0].items()}
    exe, scope = fluid.Executor(fluid.TPUPlace(0)), fluid.Scope()
    ok, done = True, 0
    with fluid.scope_guard(scope):
        exe.run(built["startup"])
        for steps in [int(n) for n in args.steps.split(",")]:
            for i in range(done, steps):
                out = exe.run(built["main"], feed=ring[i % len(ring)],
                              fetch_list=[built["loss"]], return_numpy=False)
            done = steps
            result = {
                "seed": args.seed, "steps": steps,
                "train_loss": float(np.asarray(out[0]).reshape(-1)[0]),
                "exit_step_mean": [round(float(q), 4) for q in np.asarray(
                    scope.find_var(EXIT_STAT))],
                "tolerance": adapter.TOLERANCE, "abs_diff": {},
                "passes": {}}
            got = float(np.asarray(exe.run(
                fwd["main"], feed=sample,
                fetch_list=[fwd["loss"]])[0]).reshape(-1)[0])
            result["program_loss"] = got
            params = [(p.name, scope.find_var(p.name))
                      for p in fwd["main"].global_block().all_parameters()]
            trunks = {dtype: adapter.reference_trunk(cfg, params, sample,
                                                     dtype)
                      for dtype in ("float32", "bfloat16")}
            for dtype, departure in (
                    [("float32", d) for d in (None,) + adapter.DEPARTURES]
                    + [("bfloat16", None)]):
                ref = adapter.reference_loss(cfg, params, sample, departure,
                                             dtype, trunks[dtype])
                name = departure or ("exact" if dtype == "float32"
                                     else "all_" + dtype)
                # NaN (a paired reading over its limit) as null
                result["abs_diff"][name] = (None if np.isnan(ref)
                                            else abs(got - ref))
                result["passes"][name] = bool(
                    abs(got - ref) <= adapter.TOLERANCE)
            result["ok"] = all(v == (k == "exact")
                               for k, v in result["passes"].items())
            ok = ok and result["ok"]
            print(json.dumps(result), flush=True)
            if args.save:
                os.makedirs(args.save, exist_ok=True)
                np.savez(
                    os.path.join(args.save,
                                 "%d_%d.npz" % (args.seed, steps)),
                    program=adapter.program_rows(),
                    loss_weight=sample["loss_weight"],
                    **{"%s_%s" % (dtype, part): np.asarray(v, "float32")
                       for dtype, trunk in trunks.items()
                       for part, v in zip(("costs", "z", "z_raw"), trunk)})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
