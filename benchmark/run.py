#!/usr/bin/env python3
"""Run ONE cell of BENCHMARK.json once and print the contract's last line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children: a chip belongs to one process.  Everything a
cell needs is found by name from BENCHMARK.json (the only registry):

    workloads[].name    -> benchmark/workloads/<cell>.json   (kind, mesh, shapes)
    workloads[].config  -> configs[].file                    (widths, adapter)
    config "adapter"    -> benchmark/adapters/<adapter>.py   (build, batch, flops, reference)
    workload "kind"     -> benchmark/loops/<kind>.py         (the measured loop)
    per_layer[].name    -> benchmark/layer_metrics/<name>.json -> benchmark/readers/<reader>.py

so a later PR adds a cell, a configuration or a metric with new files and
one entry, and this file has no `if` on any of their names.

Without a TPU (or with fewer chips than the cell asks for) the command
exits non-zero and prints no result.  --rehearse runs the same command at
the tiny sizes each data file carries under "rehearse", on the CPU (with
as many virtual devices as the cell has chips); it says REHEARSAL and
never prints the result line.  See benchmark/README.md.
"""

import time

T_START = time.perf_counter()  # set-up is counted from process start

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def log(msg):
    print(msg, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(subdir, name):
    """benchmark/<subdir>/<name>.py as a module, found by file path so a
    copy of benchmark/ elsewhere loads its own files."""
    path = os.path.join(BENCH_DIR, subdir, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (subdir, name), path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def merged(data, rehearse):
    """The data file as it is run: under --rehearse its "rehearse" entry
    overrides the real sizes (one level deep for nested groups)."""
    out = {k: v for k, v in data.items() if k != "rehearse"}
    if rehearse:
        for k, v in data.get("rehearse", {}).items():
            if isinstance(v, dict) and isinstance(out.get(k), dict):
                out[k] = dict(out[k], **v)
            else:
                out[k] = v
    return out


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit("benchmark: no %s named %r in BENCHMARK.json (have %s)"
                     % (what, name, ", ".join(e["name"] for e in entries)))


def cell_metrics(entries, cell):
    """The metrics of `entries` that this cell reports."""
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def read_layer_metrics(spec, cell, ctx):
    """One reader per per-layer metric; a reader that finds nothing to
    read returns None and the metric is left out of the line."""
    out = {}
    for m in cell_metrics(spec["per_layer"], cell):
        how = load_json(BENCH_DIR, "layer_metrics", m["name"] + ".json")
        reader = load_module("readers", how["reader"])
        try:
            value = reader.read(ctx, **how.get("args", {}))
        except Exception as e:  # one broken reader must not lose the run
            log("reader %s failed: %r" % (m["name"], e))
            value = None
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="measured window; default BENCHMARK.json run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; prints no result line")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's .xplane.pb into DIR")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    spec = load_json(ROOT, "BENCHMARK.json")
    cell = find(spec["workloads"], args.workload, "workload")
    cfg_entry = find(spec["configs"], cell["config"], "config")
    cfg = merged(load_json(ROOT, cfg_entry["file"]), args.rehearse)
    work = merged(load_json(BENCH_DIR, "workloads", cell["name"] + ".json"),
                  args.rehearse)
    seconds = float(spec["run_seconds"] if args.seconds is None
                    else args.seconds)
    chips = int(cell["chips"])

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d" % chips)
    sys.path.insert(0, ROOT)  # the checkout's paddle_tpu, not an installed one

    import jax

    devices = jax.devices()
    dev0 = devices[0]
    want = "cpu" if args.rehearse else "tpu"
    if dev0.platform != want:
        print("benchmark: cell %s needs a %s, jax found %s — no result"
              % (cell["name"], want, devices), file=sys.stderr)
        return 3
    if len(devices) < chips:
        print("benchmark: cell %s needs %d chips, jax found %d — no result"
              % (cell["name"], chips, len(devices)), file=sys.stderr)
        return 3

    # every program a run compiles goes to the persistent cache, however
    # fast it compiled, so a warm run's set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import paddle_tpu  # noqa: F401  places the compile cache on import

    peak = load_json(BENCH_DIR, "peaks.json").get(
        "rehearsal" if args.rehearse else dev0.device_kind)
    if peak is None:
        raise SystemExit(
            "benchmark: no peak recorded for device_kind %r — add it to "
            "benchmark/peaks.json with its source" % dev0.device_kind)

    ctx = {
        "t_start": T_START, "args": args, "seconds": seconds,
        "cell": cell, "cfg": cfg, "work": work, "chips": chips,
        "devices": devices[:chips], "rehearse": args.rehearse,
        "peak": peak,
        "root": ROOT, "log": log, "load_module": load_module,
        "adapter": load_module("adapters", cfg["adapter"]),
    }
    log("benchmark: cell %s config %s on %d x %s (%s), seed %d, window %g s,"
        " trace %d" % (cell["name"], cell["config"], chips, dev0.device_kind,
                       dev0.platform, args.seed, seconds, args.trace))
    result = load_module("loops", work["kind"]).run(ctx)

    e2e = {m["name"]: m for m in cell_metrics(spec["end_to_end"],
                                              cell["name"])}
    if args.trace:
        metrics = read_layer_metrics(spec, cell["name"], ctx)
    else:
        missing = sorted(set(e2e) - set(result["metrics"]))
        if missing:
            raise SystemExit("benchmark: loop %s reported no %s"
                             % (work["kind"], ", ".join(missing)))
        metrics = {n: {"value": float(result["metrics"][n]),
                       "unit": m["unit"]} for n, m in e2e.items()}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(result["memory_peak_bytes"])}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": device}
    trace = ctx.get("trace")
    if args.trace and trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"][:10],
                             "idle_gaps": trace["idle_gaps"][:10]}
    log("detail: " + json.dumps(result.get("detail", {}), sort_keys=True))
    if args.rehearse:
        log("rehearsal line (NOT a result): " + json.dumps(line))
        log("benchmark: REHEARSAL of %s ran to its end on the CPU — says "
            "nothing about the chip" % cell["name"])
        return 0
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
