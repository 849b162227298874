"""Shared helpers: run.py loaded as a module, and every cell's data files
at their rehearsal (tiny) sizes.  CPU only; `python -m pytest
benchmark/tests -q` from the root of the repo."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _load_run():
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(BENCH_DIR, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RUN = _load_run()
SPEC = RUN.load_json(ROOT, "BENCHMARK.json")
CELLS = [c["name"] for c in SPEC["workloads"]]
ONE_CHIP_CELLS = [c["name"] for c in SPEC["workloads"] if c["chips"] == 1]


def load_cell(cell_name, rehearse=True):
    """(cfg, work, adapter) of a cell, at its rehearsal sizes or as run."""
    cell = RUN.find(SPEC["workloads"], cell_name, "workload")
    entry = RUN.find(SPEC["configs"], cell["config"], "config")
    cfg = RUN.merged(RUN.load_json(ROOT, entry["file"]), rehearse)
    work = RUN.merged(RUN.load_json(
        BENCH_DIR, "workloads", cell_name + ".json"), rehearse)
    return cfg, work, RUN.load_module("adapters", cfg["adapter"])


def _start(bench_dir, *args):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, os.path.join(bench_dir, "run.py")] + list(args),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)



def _throwaway_copy(tmp):
    """A copy of benchmark/ plus a cell, a configuration and a per-layer
    metric made only of NEW files, and a BENCHMARK.json with their three
    entries.  Returns (copy's directory, {file: bytes} of the files that
    were there before)."""
    bench = tmp / "benchmark"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    cfg = json.loads((bench / "configs" / "tfm_base.json").read_text())
    cfg["name"] = "tfm_throwaway"
    (bench / "configs" / "tfm_throwaway.json").write_text(json.dumps(cfg))
    work = json.loads((bench / "workloads" / "tfm_base_train.json").read_text())
    work.update(batch=512, src_len=64, trg_len=64)
    work["rehearse"] = {"batch": 8, "src_len": 8, "trg_len": 8}
    (bench / "workloads" / "tfm_base_train_s64.json").write_text(
        json.dumps(work))
    (bench / "layer_metrics" / "steps_in_window.json").write_text(json.dumps(
        {"reader": "steps_in_window", "args": {"scale": 2.0}}))
    (bench / "readers" / "steps_in_window.py").write_text(
        "def read(ctx, scale):\n"
        "    return scale * len(ctx['counters']) if 'counters' in ctx "
        "else None\n")

    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({
        "name": "tfm_throwaway", "source": "test",
        "file": "benchmark/configs/tfm_throwaway.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({
        "name": "tfm_base_train_s64", "config": "tfm_throwaway",
        "traffic": "train_b512_s64", "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "steps_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Entry", "moves": "train_mfu",
        "workloads": ["tfm_base_train_s64"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and "tfm_base_train" in m["workloads"]:
            m["workloads"].append("tfm_base_train_s64")
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(bench), before


@pytest.fixture(scope="session", autouse=True)
def started_processes(request, tmp_path_factory):
    """Every process test_run.py needs, started when the session begins so
    that they run beside the in-process tests: each cell's rehearsal
    (traced, so the per-layer readers run too; the four-chip cell gets four
    virtual devices from the command itself), the real command on this
    CPU-only host, and the throwaway cell in its copy.  Nothing is started
    when no test of test_run.py is selected."""
    if not any(item.fspath.basename == "test_run.py"
               for item in request.session.items):
        yield None
        return
    rehearse = ["--seed", "3", "--seconds", "30", "--trace", "1", "--rehearse"]
    procs = {c: _start(BENCH_DIR, "--workload", c, *rehearse) for c in CELLS}
    procs["no-tpu"] = _start(BENCH_DIR, "--workload", CELLS[0], "--seed",
                             "1", "--seconds", "1", "--trace", "0")
    copy, before = _throwaway_copy(tmp_path_factory.mktemp("copy"))
    procs["throwaway"] = _start(copy, "--workload", "tfm_base_train_s64",
                                *rehearse)
    yield {"procs": procs, "before": before}
    for proc in procs.values():
        if proc.poll() is None:
            proc.kill()
            proc.wait()
