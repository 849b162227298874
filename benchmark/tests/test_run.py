"""The command itself: every cell rehearses to its end on the CPU, the
real command refuses to measure without a TPU, and a cell, a configuration
and a per-layer metric made only of NEW files run with no edit to a file
that exists (the "driven by data" requirement)."""

import json

import pytest

from conftest import CELLS, RUN, SPEC


def _rehearsal_line(out):
    tag = "rehearsal line (NOT a result): "
    lines = [ln for ln in out.splitlines() if ln.startswith(tag)]
    assert len(lines) == 1, out[-2000:]
    return json.loads(lines[0][len(tag):])


@pytest.fixture(scope="module")
def runs(started_processes):
    """(return code, stdout, stderr) of every process conftest.py started
    when the session began, by name."""
    done = {"before": started_processes["before"]}
    for name, proc in started_processes["procs"].items():
        out, err = proc.communicate(timeout=600)
        done[name] = (proc.returncode, out, err)
    return done


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_rehearses_to_its_end(runs, cell):
    rc, out, err = runs[cell]
    assert rc == 0, err[-3000:]
    assert "REHEARSAL" in out
    last = out.strip().splitlines()[-1]
    assert not last.startswith("{"), "a rehearsal prints no result line"
    line = _rehearsal_line(out)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is True, out[-3000:]
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    want = {m["name"] for m in SPEC["per_layer"]
            if m["source"] != "device_trace"
            and ("workloads" not in m or cell in m["workloads"])}
    # the CPU reports no memory, so that reader has nothing to read
    assert set(line["metrics"]) == want - {"peak_hbm_gib"}
    chips = next(c["chips"] for c in SPEC["workloads"] if c["name"] == cell)
    assert line["device"]["count"] == chips


def test_without_a_tpu_the_command_refuses(runs):
    rc, out, err = runs["no-tpu"]
    assert rc != 0
    assert "needs a tpu" in err
    assert not any(ln.startswith("{") for ln in out.splitlines())


def test_new_files_alone_make_a_cell_a_config_and_a_metric(runs):
    """A throwaway tfm_base_train_s64 on a throwaway configuration, read by
    a throwaway per-layer metric: new files in a copy of benchmark/ and
    new entries in BENCHMARK.json, no existing file edited."""
    rc, out, err = runs["throwaway"]
    assert rc == 0, err[-3000:]
    line = _rehearsal_line(out)
    assert line["correct"] is True
    assert line["metrics"]["steps_in_window"]["value"] > 0
    assert "collective_bytes" not in line["metrics"]
    for p, content in runs["before"].items():
        assert p.read_bytes() == content, "%s was edited" % p


def test_collective_bytes_agrees_with_the_programs_own_count():
    """The reader copies Executor.spmd_comm_stats' arithmetic so that the
    yardstick does not move with the program; on the same HLO text the
    two give the same bytes (sync and async forms, tuple results)."""
    from paddle_tpu.executor import Executor

    from conftest import RUN

    text = "\n".join([
        "  %ar = f32[1024,512]{1,0} all-reduce(f32[1024,512] %x), replica_groups={}",
        "  %ag = (bf16[8,128]{1,0}, bf16[8,128]) all-gather-start(bf16[4,128] %y)",
        "  %agd = bf16[8,128]{1,0} all-gather-done(%ag)",
        "  %cp = s32[16]{0} collective-permute(s32[16] %z)",
        "  %f = f32[4]{0} fusion(f32[4] %w), kind=kLoop",
    ])

    class Stub(Executor):
        def compiled_hlo(self, program):
            return [text]

    reader = RUN.load_module("readers", "collective_bytes")
    mine = reader.count([text])
    assert mine == 1024 * 512 * 4 + 8 * 128 * 2 + 16 * 4
    assert mine == Stub().spmd_comm_stats(None)["total_bytes"]


def _marks(seconds_per_interval, steps=10):
    n, t, out = 8, 0.4, [(8, 0.4)]
    for s in seconds_per_interval:
        n, t = n + steps, t + s
        out.append((n, t))
    return out


@pytest.mark.parametrize("intervals, step_s, stall_share", [
    ([0.5] * 9, 0.05, 0.0),                       # steady
    ([0.5] * 4 + [2.5] + [0.5] * 4, 0.05, 100 * 2.0 / 6.5),  # one 2 s stall
    ([0.5045] * 6 + [0.5] * 3, 4.527 / 90, 0.0),  # two modes 0.9% apart...
    ([0.5045] * 4 + [0.5] * 5, 4.518 / 90, 0.0),  # ...whichever is larger
    ([0.5] * 8 + [0.512], 0.05, 100 * 0.012 / 4.512),  # 2.4% over: a stall
])
def test_pace_leaves_out_stalled_intervals_and_keeps_them_beside_it(
        intervals, step_s, stall_share):
    """The loop's pace: one stall in a window moves work / window and not
    the pace; what is left out is stall_share; the pace moves smoothly
    with the share of each of two near modes (a median would jump)."""
    train = RUN.load_module("loops", "train")
    got_step, got_stall, n = train.pace(_marks(intervals), 98, 5.0)
    assert n == len(intervals)
    assert got_step == pytest.approx(step_s)
    assert got_stall == pytest.approx(stall_share, abs=1e-9)


def test_pace_of_a_window_with_one_read_back_is_the_whole_window():
    train = RUN.load_module("loops", "train")
    assert train.pace([(8, 0.4)], 12, 0.6) == (pytest.approx(0.05),
                                               pytest.approx(0.0), 1)
