"""Aux subsystems: flags (+check_nan_inf), debugger dumps, fault-tolerant
master task queue, bf16 AMP rewrite."""

import os
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers
from paddle_tpu.contrib.mixed_precision import rewrite_bf16
from paddle_tpu.distributed import Master, MasterClient
from paddle_tpu.distributed.rpc import RPCClient


def _mlp():
    x = layers.data("x", shape=[4])
    y = layers.data("y", shape=[1])
    pred = layers.fc(layers.fc(x, size=8, act="relu"), size=1)
    return layers.mean(layers.square_error_cost(pred, y))


# ---------------------------------------------------------------------------
def test_flags_registry_and_env():
    assert flags.get_flag("rpc_deadline") == 180000
    flags.set_flags({"FLAGS_rpc_deadline": "5000", "max_retry": 2})
    assert flags.get_flag("rpc_deadline") == 5000
    assert flags.get_flag("max_retry") == 2
    with pytest.raises(KeyError):
        flags.set_flags({"not_a_flag": 1})
    flags.set_flags({"rpc_deadline": 180000, "max_retry": 30})
    assert "check_nan_inf" in flags.flag_items()


def test_check_nan_inf_flag():
    loss = _mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    bad = np.full((4, 4), np.nan, "float32")
    y = np.zeros((4, 1), "float32")
    flags.set_flags({"check_nan_inf": True})
    try:
        with pytest.raises(RuntimeError, match="NaN/Inf"):
            exe.run(feed={"x": bad, "y": y}, fetch_list=[loss])
    finally:
        flags.set_flags({"check_nan_inf": False})


# ---------------------------------------------------------------------------
def test_debugger_dumps(tmp_path):
    from paddle_tpu import debugger

    loss = _mlp()
    prog = fluid.default_main_program()
    text = debugger.pprint_program_codes(prog)
    assert "mul(" in text and "x[-1x4,float32]" in text
    dot_path = str(tmp_path / "g.dot")
    dot = debugger.draw_block_graphviz(
        prog.global_block(), highlights=[loss.name], path=dot_path
    )
    assert dot.startswith("digraph G {") and "lightcoral" in dot
    assert os.path.exists(dot_path)


# ---------------------------------------------------------------------------
def test_master_task_queue_lease_finish_and_timeout(tmp_path):
    snap = str(tmp_path / "master.json")
    master = Master("127.0.0.1:0", timeout_s=0.5, failure_max=3,
                    snapshot_path=snap, chunks_per_task=2)
    try:
        cli = MasterClient(master.endpoint, trainer_id=0)
        cli.set_dataset(["c%d" % i for i in range(6)])  # 3 tasks of 2

        t1, p1 = cli.get_task()
        assert sorted(p1) == ["c0", "c1"]
        cli.task_finished(t1)

        # lease a task and let it time out (dead trainer)
        t2, _ = cli.get_task()
        time.sleep(0.7)
        # after timeout the task re-queues; drain everything
        seen = set()
        while True:
            tid, payload = cli.get_task()
            if tid is None:
                break
            seen.add(tid)
            cli.task_finished(tid)
        assert t2 in seen  # the timed-out lease came back
        assert cli.epoch_done()
        s = cli.stats()
        assert s["done"] == 3 and s["todo"] == 0 and s["pending"] == 0
    finally:
        master.shutdown()

    # snapshot restore: a new master resumes with completed state
    master2 = Master("127.0.0.1:0", snapshot_path=snap)
    try:
        RPCClient.reset_all()
        cli2 = MasterClient(master2.endpoint)
        s = cli2.stats()
        assert s["done"] == 3 and s["todo"] == 0
    finally:
        master2.shutdown()
        RPCClient.reset_all()


def test_master_failure_max_discards(tmp_path):
    master = Master("127.0.0.1:0", timeout_s=30, failure_max=2)
    try:
        RPCClient.reset_all()
        cli = MasterClient(master.endpoint)
        cli.set_dataset(["only"])
        for _ in range(2):  # fail it failure_max times
            tid, _ = cli.get_task()
            assert tid is not None
            cli.task_failed(tid)
        tid, _ = cli.get_task()
        assert tid is None and cli.epoch_done()  # discarded, not re-queued
    finally:
        master.shutdown()
        RPCClient.reset_all()


# ---------------------------------------------------------------------------
def test_bf16_amp_rewrite_trains_and_matches_f32():
    rng = np.random.RandomState(0)
    xv = rng.rand(16, 4).astype("float32")
    yv = (xv @ np.array([[1.0], [-2.0], [3.0], [0.5]], "float32"))

    def run(amp):
        import paddle_tpu.framework as fw
        from paddle_tpu.core import scope as scope_mod
        from paddle_tpu import unique_name

        fw.switch_main_program(fluid.Program())
        fw.switch_startup_program(fluid.Program())
        unique_name.switch()
        scope_mod._switch_scope(scope_mod.Scope())
        fluid.default_main_program().random_seed = 7
        fluid.default_startup_program().random_seed = 7
        loss = _mlp()
        n = rewrite_bf16() if amp else 0
        # lr/steps sized so the halving bar below has real margin: at
        # SGD(0.05) x 10 steps BOTH precisions only reach ~0.60x (the
        # old bar failed for f32 and bf16 alike — a convergence-budget
        # problem, not a precision one); 0.1 x 20 reaches ~0.31x with
        # the bf16-vs-f32 trajectory gap still ~0.4% << the 15% parity
        # tolerance
        fluid.optimizer.SGD(0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        losses = [
            float(np.ravel(exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])[0])[0])
            for _ in range(20)
        ]
        return losses, n

    f32_losses, _ = run(False)
    amp_losses, n_rewritten = run(True)
    assert n_rewritten == 2  # both fc muls
    assert amp_losses[-1] < amp_losses[0] * 0.5  # trains
    # bf16 has ~3 decimal digits: trajectories agree loosely
    np.testing.assert_allclose(amp_losses, f32_losses, rtol=0.15, atol=0.02)
    # and the rewritten program actually contains bf16 casts
    types = [op.type for op in fluid.default_main_program().global_block().ops]
    assert types.count("cast") >= 4


def test_memory_and_device_info_surfaces():
    """HBM stats + device info layer (SURVEY §2.7/§2.8 re-expression)."""
    import paddle_tpu as fluid

    assert fluid.device_info.cpu_count() >= 1
    assert fluid.device_info.device_count() >= 1
    assert isinstance(fluid.device_info.device_kind(), str)
    stats = fluid.memory.memory_stats()
    assert isinstance(stats, dict)
    assert fluid.memory.memory_allocated() >= 0
    assert fluid.memory.max_memory_allocated() >= fluid.memory.memory_allocated() or not stats


def test_memory_fraction_env_wiring(monkeypatch):
    import paddle_tpu.memory as mem

    monkeypatch.delenv("XLA_PYTHON_CLIENT_MEM_FRACTION", raising=False)
    monkeypatch.setenv("FLAGS_fraction_of_gpu_memory_to_use", "0.5")
    mem.apply_memory_fraction()
    import os

    assert os.environ["XLA_PYTHON_CLIENT_MEM_FRACTION"] == "0.5"


def test_lowering_error_carries_op_context():
    """enforce.h-style error context: a shape error inside the compiled
    block names the op, block index, and input shapes.  With the static
    verifier armed (FLAGS_check_program) the same defect is caught
    BEFORE tracing, as an attributable diagnostic; the trace-time
    context machinery is exercised with the flag pinned off."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import flags, layers
    from paddle_tpu.analysis import ProgramVerifyError

    x = layers.data("ec_x", shape=[3, 4], append_batch_size=False)
    y = layers.data("ec_y", shape=[5, 6], append_batch_size=False)
    out = layers.matmul(x, y)
    exe = fluid.Executor(fluid.CPUPlace())
    import pytest

    feed = {
        "ec_x": np.ones((3, 4), "float32"),
        "ec_y": np.ones((5, 6), "float32"),
    }
    old = flags.get_flag("check_program")
    flags.set_flags({"check_program": True})
    try:
        with pytest.raises(ProgramVerifyError,
                           match=r"\[shape-mismatch\].*\(matmul\)"):
            exe.run(feed=feed, fetch_list=[out])
    finally:
        flags.set_flags({"check_program": old})
    flags.set_flags({"check_program": False})
    try:
        with pytest.raises(RuntimeError, match="lowering op 'matmul'.*shapes"):
            exe.run(feed=feed, fetch_list=[out])
    finally:
        flags.set_flags({"check_program": old})


def test_nested_lod_two_levels():
    """2-level LoD: [doc -> sents -> tokens] padded to
    [docs, max_sents, max_toks] with per-sentence lengths."""
    import numpy as np
    from paddle_tpu.lod import create_lod_tensor

    data = np.arange(10, dtype="float32").reshape(10, 1)
    # doc0 has 2 sentences (3 + 2 tokens), doc1 has 1 sentence (5 tokens)
    t = create_lod_tensor(data, recursive_seq_lens=[[2, 1], [3, 2, 5]])
    assert t.lod_level() == 2
    assert t.data.shape == (2, 2, 5, 1)
    np.testing.assert_array_equal(t.nested_seq_lens, [[3, 2], [5, 0]])
    np.testing.assert_allclose(t.data[0, 0, :3, 0], [0, 1, 2])
    np.testing.assert_allclose(t.data[0, 1, :2, 0], [3, 4])
    np.testing.assert_allclose(t.data[1, 0, :, 0], [5, 6, 7, 8, 9])
    np.testing.assert_array_equal(t.seq_lens(0), [2, 1])
    np.testing.assert_array_equal(t.seq_lens(1), [3, 2, 5])


def test_nested_lod_three_levels():
    """N-level LoD composition (lod_tensor.h:58's arbitrary recursion):
    3 levels [corpus -> docs -> sents -> tokens] pad to
    [corpora, max_docs, max_sents, max_toks, *feat] with a per-level
    padded lengths pyramid in `padded_lens`."""
    import numpy as np
    from paddle_tpu.lod import create_lod_tensor

    data = np.arange(12, dtype="float32").reshape(12, 1)
    # corpus0: 2 docs (doc0: 2 sents of 2+1 toks; doc1: 1 sent of 3)
    # corpus1: 1 doc  (doc2: 2 sents of 4+2 toks)
    t = create_lod_tensor(
        data,
        recursive_seq_lens=[[2, 1], [2, 1, 2], [2, 1, 3, 4, 2]],
    )
    assert t.lod_level() == 3
    assert t.data.shape == (2, 2, 2, 4, 1)
    # level-0: docs per corpus
    np.testing.assert_array_equal(t.padded_lens[0], [2, 1])
    # level-1: sents per doc, padded to [corpora, max_docs]
    np.testing.assert_array_equal(t.padded_lens[1], [[2, 1], [2, 0]])
    # level-2: tokens per sent, padded to [corpora, max_docs, max_sents]
    np.testing.assert_array_equal(
        t.padded_lens[2],
        [[[2, 1], [3, 0]], [[4, 2], [0, 0]]],
    )
    np.testing.assert_allclose(t.data[0, 0, 0, :2, 0], [0, 1])
    np.testing.assert_allclose(t.data[0, 0, 1, :1, 0], [2])
    np.testing.assert_allclose(t.data[0, 1, 0, :3, 0], [3, 4, 5])
    np.testing.assert_allclose(t.data[1, 0, 0, :4, 0], [6, 7, 8, 9])
    np.testing.assert_allclose(t.data[1, 0, 1, :2, 0], [10, 11])
    # untouched slots are zero padding
    assert float(np.abs(t.data[1, 1]).sum()) == 0.0
    np.testing.assert_array_equal(t.seq_lens(0), [2, 1])
    np.testing.assert_array_equal(t.seq_lens(2), [2, 1, 3, 4, 2])
    # mismatched level sums still raise
    import pytest

    with pytest.raises(ValueError, match="level-0"):
        create_lod_tensor(data, recursive_seq_lens=[[2], [2, 1, 2],
                                                    [2, 1, 3, 4, 2]])


def test_api_spec_stability():
    """tools/diff_api.py CI contract: the live public API covers the
    committed API.spec snapshot (removals/re-signatures fail)."""
    import subprocess
    import sys
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "diff_api.py"),
         os.path.join(root, "API.spec")],
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_ploter_csv_fallback_and_api(tmp_path):
    """utils.plot.Ploter (python/paddle/utils/plot.py parity): append/plot/
    reset; files land whether or not matplotlib exists."""
    from paddle_tpu.utils.plot import Ploter

    p = Ploter("train cost", "test cost")
    for i in range(5):
        p.append("train cost", i, 1.0 / (i + 1))
    p.append("test cost", 0, 0.5)
    out = str(tmp_path / "curve.png")
    p.plot(out)
    import os
    produced = os.listdir(str(tmp_path))
    assert produced, "plot() wrote nothing"
    p.reset()
    assert p.__plot_data__["train cost"].step == []


def test_dataset_image_transforms():
    """dataset.image (python/paddle/dataset/image.py parity): resize_short
    keeps aspect, crops/flip/chw/mean behave, and the pipeline is
    deterministic for eval."""
    from paddle_tpu.dataset import image as img

    rng = np.random.RandomState(0)
    im = (rng.rand(40, 60, 3) * 255).astype("uint8")

    r = img.resize_short(im, 20)
    assert r.shape[:2] == (20, 30)  # shorter edge 40 -> 20, aspect kept
    r2 = img.resize_short(im.transpose(1, 0, 2), 20)
    assert r2.shape[:2] == (30, 20)

    c = img.center_crop(r, 16)
    assert c.shape[:2] == (16, 16)
    rc = img.random_crop(r, 16, rng=np.random.RandomState(3))
    assert rc.shape[:2] == (16, 16)

    f = img.left_right_flip(c)
    np.testing.assert_array_equal(f[:, 0], c[:, -1])

    chw = img.to_chw(c)
    assert chw.shape == (3, 16, 16)

    out = img.simple_transform(im, 24, 16, is_train=False,
                               mean=[1.0, 2.0, 3.0])
    assert out.shape == (3, 16, 16) and out.dtype == np.float32
    out2 = img.simple_transform(im, 24, 16, is_train=False,
                                mean=[1.0, 2.0, 3.0])
    np.testing.assert_array_equal(out, out2)  # eval path deterministic

    # train path with a fixed rng is reproducible too
    t1 = img.simple_transform(im, 24, 16, True, rng=np.random.RandomState(5))
    t2 = img.simple_transform(im, 24, 16, True, rng=np.random.RandomState(5))
    np.testing.assert_array_equal(t1, t2)

    # grayscale path
    g = img.resize_short(im[:, :, 0], 20)
    assert g.shape == (20, 30)

    # bilinear sanity: resize of a constant image stays constant
    const = np.full((10, 14, 3), 7, "uint8")
    rr = img.resize_short(const, 5)
    assert np.all(rr == 7)


def test_dataset_image_decode_roundtrip(tmp_path):
    """load_image / load_image_bytes decode an encoded PNG back to the
    original pixels (PIL-backed IO convenience)."""
    from PIL import Image

    from paddle_tpu.dataset import image as img

    rng = np.random.RandomState(1)
    arr = (rng.rand(8, 9, 3) * 255).astype("uint8")
    p = tmp_path / "t.png"
    Image.fromarray(arr).save(str(p))
    got = img.load_image(str(p))
    np.testing.assert_array_equal(got, arr)
    gray = img.load_image(str(p), is_color=False)
    assert gray.shape == (8, 9)


def test_image_resize_rounds_not_truncates():
    """uint8 bilinear resize rounds to nearest (PIL/cv2 parity) instead of
    truncation-darkening."""
    from paddle_tpu.dataset import image as img

    im = np.full((4, 6, 3), 201, "uint8")
    im[::2] = 202  # interpolated rows land at ~201.5
    out = img.resize_short(im, 3)
    assert out.dtype == np.uint8
    # every output pixel must be one of the neighbors or the ROUNDED mid
    assert set(np.unique(out)) <= {201, 202}
    mid = img._bilinear_resize(
        np.array([[100, 101]], "uint8").reshape(1, 2), 1, 3
    )
    assert mid.flatten().tolist()[1] in (100, 101)  # rounded, never 99


def test_packaging_metadata_builds():
    """pyproject.toml is a valid setuptools package definition: the
    package set resolves to paddle_tpu.* with the native sources included
    (the reference's wheel/cmake packaging role, python-side)."""
    import os

    import setuptools

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(os.path.join(repo, "pyproject.toml"))
    try:
        import tomllib
    except ImportError:
        import tomli as tomllib
    with open(os.path.join(repo, "pyproject.toml"), "rb") as f:
        cfg = tomllib.load(f)
    assert cfg["project"]["name"] == "paddle-tpu"
    pkgs = setuptools.find_packages(repo, include=["paddle_tpu*"])
    assert "paddle_tpu" in pkgs and "paddle_tpu.ops" in pkgs
    assert "tests" not in pkgs
    data = cfg["tool"]["setuptools"]["package-data"]["paddle_tpu.native"]
    assert "*.cc" in data and "Makefile" in data


def test_per_op_timeline_correlated_tracks(tmp_path):
    """per_op_timeline (device_tracer + tools/timeline.py capability): one
    chrome trace with host+device tracks sharing a correlation id per op,
    and a per-op table sorted by device time."""
    import json

    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers, profiler

    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x = layers.data("x", shape=[16])
        y = layers.fc(layers.fc(x, 32, act="relu"), 4)
        loss = layers.mean(y)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        path = str(tmp_path / "perop.json")
        rows = profiler.per_op_timeline(
            main, {"x": np.random.rand(4, 16).astype("float32")},
            scope=scope, path=path)
    assert rows and all(len(r) == 4 for r in rows)
    types = {r[0] for r in rows}
    assert "mul" in types and "mean" in types
    trace = json.load(open(path))["traceEvents"]
    spans = [e for e in trace if e.get("ph") == "X"]
    host = {e["args"]["correlation"] for e in spans if e["tid"] == 1}
    dev = {e["args"]["correlation"] for e in spans if e["tid"] == 2}
    assert host == dev and len(host) == len(rows)
    # device rows are the sort key
    assert rows == sorted(rows, key=lambda r: -r[3])


def test_comm_compute_split_attributes_phase_spans():
    """Wire-compression observability: cat-tagged serialize/compress/
    apply spans surface as their own phase lines in comm_compute_split
    instead of lumping into comm — and stay absent when no such spans
    were recorded."""
    from paddle_tpu import profiler

    rows = [("send_bucket", 0, 4.0, 4.0), ("mul", 1, 6.0, 6.0)]
    base = profiler.comm_compute_split(rows, events=[])
    assert base["comm_ms"] == 4.0 and base["compute_ms"] == 6.0
    assert not any(k.endswith("_ms") and k not in ("comm_ms", "compute_ms")
                   for k in base)
    events = [
        {"name": "rpc_serialize", "cat": "serialize", "dur": 1500.0},
        {"name": "wire_compress", "cat": "compress", "dur": 250.0},
        {"name": "ps_apply_round", "cat": "apply", "dur": 3000.0},
        {"name": "rpc_send", "cat": "comm", "dur": 9000.0},  # not a phase
    ]
    out = profiler.comm_compute_split(rows, events=events)
    assert out["serialize_ms"] == 1.5
    assert out["compress_ms"] == 0.25
    assert out["apply_ms"] == 3.0
    # real spans: the profiler's captured events feed the split by default
    profiler.reset_profiler()
    profiler.start_profiler("CPU", None)
    try:
        with profiler.RecordEvent("rpc_serialize", cat="serialize"):
            import time as _time

            _time.sleep(0.002)
    finally:
        profiler.stop_profiler(profile_path=None)
    assert "serialize_ms" in profiler.comm_compute_split(rows)
    profiler.reset_profiler()


def test_timeline_tool_merges_worker_profiles(tmp_path):
    """tools/timeline.py (reference tools/timeline.py:160 role): merge
    per-worker profiler JSONs into one trace with per-process lanes."""
    import json
    import subprocess
    import sys

    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers, profiler

    paths = []
    for i in range(2):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.framework.program_guard(main, startup):
            x = layers.data("x", shape=[4])
            y = layers.fc(x, 2)
        scope = fluid.Scope()
        p = str(tmp_path / ("w%d.json" % i))
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            with profiler.profiler("CPU", profile_path=p):
                exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                        fetch_list=[y])
        paths.append(p)

    out = str(tmp_path / "merged.json")
    r = subprocess.run(
        [sys.executable, "tools/timeline.py", "--out", out,
         "trainer0=%s" % paths[0], "pserver0=%s" % paths[1]],
        cwd="/root/repo", timeout=120,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    assert r.returncode == 0, r.stdout.decode()
    trace = json.load(open(out))["traceEvents"]
    names = {e["args"]["name"] for e in trace if e.get("ph") == "M"}
    assert {"trainer0", "pserver0"} <= names
    pids = {e["pid"] for e in trace}
    assert pids == {0, 1}
    assert any(e.get("ph") == "X" for e in trace)


def test_op_coverage_vs_reference():
    """Every reference REGISTER_OPERATOR type is lowered, generically
    derived, or on the documented structural/N-A list
    (tools/check_op_coverage.py — the op-level diff_api.py sibling)."""
    import os
    import subprocess
    import sys

    if not os.path.isdir("/root/reference"):
        import pytest

        pytest.skip("reference tree unavailable")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "check_op_coverage.py")],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH", "")},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_generator_follows_the_place_not_a_flag(monkeypatch):
    """Which generator draws in-program randomness is Executor._rng_impl's
    answer for the platform the step is placed on (rbg on a TPU, the raw
    threefry key elsewhere), not a flag.  With the TPU's answer forced on
    this host: dropout still masks at ~rate with correct scaling, the
    run()/run_loop() stream parity contract holds (both draw
    fold_in(base, step)), and the stream differs from threefry's."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import flags, layers

    assert "prng_impl" not in flags.flag_items()
    x = np.ones((64, 256), dtype="float32")

    def masked(impl):
        monkeypatch.setattr(fluid.Executor, "_rng_impl",
                            staticmethod(lambda platform: impl))
        main, startup = fluid.Program(), fluid.Program()
        with fluid.framework.program_guard(main, startup):
            inp = layers.data("x", shape=[256])
            out = layers.dropout(inp, dropout_prob=0.4)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            (v,) = exe.run(main, feed={"x": x}, fetch_list=[out])
            # run_loop must draw the SAME per-step keys as run()
            exe2 = fluid.Executor(fluid.CPUPlace())
            exe2.run(startup)  # align step counters with exe
            (v_loop,) = exe2.run_loop(1, main, feed={"x": x},
                                      fetch_list=[out])
        return np.asarray(v), np.asarray(v_loop)

    rbg, rbg_loop = masked("rbg")
    fry, fry_loop = masked("threefry")
    for v in (rbg, fry):
        rate = float((v == 0).mean())
        assert 0.3 < rate < 0.5, rate
        nz = v[v != 0]
        np.testing.assert_allclose(nz, nz[0], rtol=1e-6)  # 1/(1-p) scale
    np.testing.assert_allclose(rbg, rbg_loop)
    np.testing.assert_allclose(fry, fry_loop)
    assert (rbg == 0).sum() != 0 and not np.array_equal(rbg == 0, fry == 0)


def test_tpu_place_raises_on_a_host_without_tpu():
    """TPUPlace never falls back to whatever backend exists: a program
    that asked for the chip and silently ran on the host is how CPU
    timings came to be recorded under TPU metric names."""
    import pytest

    import paddle_tpu as fluid

    with pytest.raises(RuntimeError, match="no TPU"):
        fluid.TPUPlace(0).jax_device()
    assert isinstance(fluid.default_place(), fluid.CPUPlace)


def test_compile_cache_placed_from_outside_or_at_the_fixed_path():
    """One site places JAX's persistent compilation cache: a directory
    named by the environment is left to JAX; otherwise the fixed
    in-checkout path (part of the cache key, so it must not move); a
    CPU-pinned process gets none."""
    import os

    import jax

    from paddle_tpu import compile_cache as cc

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.resolve_cache_dir({}) == (
        os.path.join(root, ".jax_cache"), False)
    assert cc.resolve_cache_dir({"JAX_PLATFORMS": "cpu"}) == (None, False)
    assert cc.resolve_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/x", "JAX_PLATFORMS": "cpu"}) == (
            "/x", True)
    before = jax.config.jax_compilation_cache_dir
    assert cc.apply_compile_cache({"JAX_COMPILATION_CACHE_DIR": "/x"}) == "/x"
    assert jax.config.jax_compilation_cache_dir == before  # untouched
    # the tree has exactly one site that sets the option
    import subprocess

    found = subprocess.run(
        ["grep", "-rl", "--include=*.py", "jax_compilation_cache_dir",
         "paddle_tpu", "tools", "scripts", "examples",
         "__graft_entry__.py"],
        cwd=root, capture_output=True, text=True).stdout.split()
    assert found == ["paddle_tpu/compile_cache.py"], found


def _run_chip_smoke(*args):
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py")] + list(args),
        env=env, cwd=root, capture_output=True, text=True, timeout=600)


def test_chip_smoke_refuses_to_run_without_a_chip():
    proc = _run_chip_smoke()
    assert proc.returncode != 0
    assert "needs a tpu" in proc.stdout
    assert '"ok"' not in proc.stdout


@pytest.mark.slow  # full rehearsal (~40 s); rides the ci.sh kernel pass
def test_chip_smoke_rehearsal_runs_every_phase_and_never_passes():
    proc = _run_chip_smoke("--rehearse")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "REHEARSAL ok" in proc.stdout
    for phase in ("train", "numerics", "kernels"):
        assert "-- %s ok" % phase in proc.stdout
    assert '"ok"' not in proc.stdout
