"""Compile one benchmark cell's train step, at its real sizes, for a
DESCRIBED (not attached) TPU v5e on this host, and print the TPU
compiler's memory counts: what the chip's compiler would refuse costs no
chip time (on-chip-measurement guide, section 2).  Nothing runs: no time,
no result.  One-chip cells only.  Pallas kernels are compiled as Mosaic
calls, as on the chip (until PR 46 they were interpreted here, and the
counts read 0.05 GiB lower on `kimi_linear_48b_a3b_train`).  The step is
jitted by the Executor's own helper (core/trace.jit_step: the read-write
state's layouts are the compiler's), so the counts and `--hlo` are of the
step that runs; `state_relayouts` names the arrays the compiler takes in
another layout than the device's default, and the last line sums the
`copy` instructions of the entry computation (a transposing copy of a
parameter is the state's layout not suiting the step).

    JAX_PLATFORMS=cpu python tools/compile_cell_for_chip.py \
        --workload kanana2_30b_a3b_train [--seq-len 6144]

The process that describes the topology holds libtpu until it exits."""

import argparse
import importlib.util
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


_ITEM = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1,
         "pred": 1, "f64": 8, "s64": 8, "u64": 8}


def entry_copies(text):
    """Bytes each `copy` instruction of the ENTRY computation writes."""
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    sizes = []
    for dtype, dims in re.findall(
            r"= (\w+)\[([0-9,]*)\][^ ]* copy\(", entry):
        n = _ITEM.get(dtype, 4)
        for d in dims.split(","):
            n *= int(d) if d else 1
        sizes.append(n)
    return sizes


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seq-len", type=int, default=None,
                    help="override the cell's seq_len")
    ap.add_argument("--hlo", default=None,
                    help="write the optimized HLO text here")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as fluid
    from paddle_tpu.core.trace import build_traced_function, jit_step
    from paddle_tpu.ops import pallas_kernels

    # the Pallas kernels as the chip compiles them: this host's backend is
    # the CPU, where they would be interpreted, and the count would be of a
    # program that never runs (PERF.md section 6, PR 46)
    pallas_kernels._interpret = lambda: False

    # benchmark/run.py as a module: the registry is read as it reads it
    run_spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(ROOT, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(run_spec)
    run_spec.loader.exec_module(run)
    spec = run.load_json(ROOT, "BENCHMARK.json")
    cell = run.find(spec["workloads"], args.workload, "workload")
    if int(cell["chips"]) != 1:
        raise SystemExit("compile_cell_for_chip: one-chip cells only")
    cfg = run.merged(run.load_json(ROOT, run.find(
        spec["configs"], cell["config"], "config")["file"]), False)
    work = run.merged(run.load_json(
        run.BENCH_DIR, "workloads", cell["name"] + ".json"), False)
    if args.seq_len:
        work["seq_len"] = args.seq_len
    adapter = run.load_module("adapters", cfg["adapter"])

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    built = adapter.build(cfg, work)
    main_p, startup = built["main"], built["startup"]
    scope = fluid.Scope()
    for block in (main_p.global_block(), startup.global_block()):
        for name, var in block.vars.items():
            if var.persistable and all(int(d) >= 0 for d in var.shape):
                scope.set(name, jax.ShapeDtypeStruct(
                    tuple(int(d) for d in var.shape),
                    jnp.dtype(str(var.dtype))))
    batch = adapter.make_batch(cfg, work, 0)
    traced = build_traced_function(
        main_p, 0, tuple(sorted(batch)), [built["loss"].name], scope,
        platform="tpu")

    def shaped(n):
        v = scope.find_var(n)
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip)

    key = jax.eval_shape(lambda: jax.random.key(1, impl="rbg"))
    rw = {n: shaped(n) for n in traced.rw_names}
    compiled = jit_step(traced, {n: chip for n in rw}).lower(
        {n: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
         for n, a in batch.items()},
        {n: shaped(n) for n in traced.ro_names}, rw,
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=chip),
    ).compile()
    taken = compiled.input_formats[0][2]
    default = jax.jit(lambda state: state).lower(rw).compile(
        ).input_formats[0][0]
    relaid = sorted(n for n in rw if taken[n].layout != default[n].layout)
    m = compiled.memory_analysis()
    gib = 2.0 ** 30
    print("cell %s seq_len %s: arguments %.2f GiB, temporaries %.2f GiB, "
          "output %.2f GiB, aliased %.2f GiB, generated code %.2f GiB: "
          "arguments + temporaries + output - aliased = %.2f GiB"
          % (cell["name"], work.get("seq_len", "-"),
             m.argument_size_in_bytes / gib, m.temp_size_in_bytes / gib,
             m.output_size_in_bytes / gib, m.alias_size_in_bytes / gib,
             m.generated_code_size_in_bytes / gib,
             (m.argument_size_in_bytes + m.temp_size_in_bytes
              + m.output_size_in_bytes - m.alias_size_in_bytes) / gib))
    print("state_relayouts: %d of %d read-write arrays" % (len(relaid),
                                                           len(rw)))
    by_kind = {}
    for n in relaid:
        by_kind.setdefault((str(rw[n].dtype), tuple(rw[n].shape),
                            taken[n].layout.major_to_minor), []).append(n)
    for (dtype, shape, order), names in sorted(by_kind.items()):
        print("  %d x %s%s -> major to minor %s: %s%s" % (
            len(names), dtype, list(shape), list(order),
            ", ".join(names[:3]), ", ..." if len(names) > 3 else ""))
    text = compiled.as_text()
    print("tpu_custom_call instructions: %d" % text.count("tpu_custom_call"))
    copies = entry_copies(text)
    print("entry computation: %d copy instructions, %.2f GB written"
          % (len(copies), sum(copies) / 1e9))
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)


if __name__ == "__main__":
    main()
