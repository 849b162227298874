"""trace_reduce.py against a small trace whose numbers are computed by
hand.  data/small_trace.pbtxt (an XSpace in text form, times in
microseconds) holds one TPU plane and the host plane:

  XLA Modules  jit_step(7) runs [0,100] [150,250] [300,400] [450,550],
               jit_fold(3) [260,262]
  XLA Ops      (named, as on the chip, by their whole HLO instruction)
               run 1: fusion.1 [0,100]
               run 2: fusion.1 [150,200] all-reduce.1 [200,230] fusion.2 [225,250]
               copy.3 [260,262]
               run 3: fusion.1 [300,350] all-reduce.1 [350,380] fusion.2 [380,400]
               run 4: fusion.1 [450,500] all-reduce.1 [500,530] fusion.2 [530,550]
  host python  bench:run [255,290] bench:readback [395,445] bench:feed [446,449]

The steady window leaves out run 1: [150,550] = 400 us, 3 steps.  Busy is
the union of the op intervals inside it: 100 + 2 + 100 + 100 = 302 us (the
all-reduce / fusion.2 overlap of run 2 counts once), so idle is 98 us =
24.5%.  Gaps: [400,450] 50 us under bench:readback (45 of it), [262,300]
38 us under bench:run, [250,260] 10 us under bench:run.
"""

import os

import pytest

from conftest import BENCH_DIR, RUN

DATA = os.path.join(BENCH_DIR, "tests", "data")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    tr = RUN.load_module("", "trace_reduce")
    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        return tr.reduce_profile(ProfileData.from_text_proto(f.read()))


def test_window_busy_and_idle(reduced):
    assert reduced["window_s"] == pytest.approx(400e-6)
    assert reduced["busy_s"] == pytest.approx(302e-6)
    assert reduced["idle_share"] == pytest.approx(0.245)
    assert reduced["steps"] == 3


def test_top_operations(reduced):
    ops = reduced["device_ops"]
    assert [n for n, _ in ops] == [
        "%fusion.1 fusion kOutput (f32[8]{0}, bf16[8,4]{1,0})",
        "%all-reduce.1 all-reduce f32[1024]{0}",
        "%fusion.2 fusion kLoop bf16[8,4]{1,0}",
        "%copy.3 copy u32[2]{0}"]
    assert [t for _, t in ops] == pytest.approx([150e-6, 90e-6, 65e-6, 2e-6])
    assert reduced["op_categories"][:2] == [
        ["fusion kOutput", pytest.approx(150e-6)],
        ["all-reduce", pytest.approx(90e-6)]]


def test_idle_gaps_carry_the_host_span(reduced):
    assert [n for n, _ in reduced["idle_gaps"]] == [
        "bench:readback", "bench:run", "bench:run"]
    assert [t for _, t in reduced["idle_gaps"]] == pytest.approx(
        [50e-6, 38e-6, 10e-6])


def test_collective_share(reduced):
    assert reduced["collective_share"] == pytest.approx(90.0 / 302.0)


def test_a_trace_without_a_device_reduces_to_nothing():
    from jax.profiler import ProfileData

    tr = RUN.load_module("", "trace_reduce")
    text = 'planes { id: 1 name: "/host:CPU" }'
    assert tr.reduce_profile(ProfileData.from_text_proto(text)) is None
