"""Native C++ runtime: recordio roundtrip + C++/Python format interop,
blocking queue concurrency, threaded prefetch loader."""

import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import native, recordio

# multi-process / full-train-cycle integration tests: excluded from the
# default fast run (pytest.ini addopts -m "not slow"); run with -m "" 
pytestmark = pytest.mark.slow


def test_native_builds():
    assert native.available(), "native library failed to build"


def _write_with(writer_cls, path, records):
    w = writer_cls(path, recordio.COMPRESSOR_ZLIB, 3)  # small chunks
    for r in records:
        w.write(r)
    w.close()


@pytest.mark.parametrize("writer_native", [True, False])
@pytest.mark.parametrize("scanner_native", [True, False])
def test_recordio_interop(tmp_path, writer_native, scanner_native):
    """Files written by either side read back identically on either side."""
    if (writer_native or scanner_native) and not native.available():
        pytest.skip("no native lib")
    path = str(tmp_path / "data.recordio")
    records = [bytes([i]) * (i * 37 + 1) for i in range(10)]
    wcls = recordio._NativeWriter if writer_native else recordio._PyWriter
    scls = recordio._NativeScanner if scanner_native else recordio._PyScanner
    _write_with(wcls, path, records)
    got = list(scls(path))
    assert got == records


def test_recordio_sample_roundtrip(tmp_path):
    path = str(tmp_path / "samples.recordio")
    rng = np.random.RandomState(0)
    samples = [(rng.rand(3, 4).astype("float32"), np.int64(i)) for i in range(7)]
    n = recordio.convert_reader_to_recordio_file(path, lambda: iter(samples))
    assert n == 7
    back = list(recordio.recordio_reader(path)())
    assert len(back) == 7
    for (a, b), (a2, b2) in zip(samples, back):
        np.testing.assert_array_equal(a, a2)
        assert int(b) == int(b2)


def test_blocking_queue_concurrent():
    if not native.available():
        pytest.skip("no native lib")
    q = native.BlockingQueue(capacity=4)
    items = [("item-%04d" % i).encode() for i in range(200)]
    got = []

    def producer():
        for it in items:
            assert q.push(it)
        q.close()

    def consumer():
        while True:
            v = q.pop()
            if v is None:
                return
            got.append(v)

    threads = [threading.Thread(target=producer)] + [
        threading.Thread(target=consumer) for _ in range(3)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert sorted(got) == sorted(items)


def test_blocking_queue_timeout():
    if not native.available():
        pytest.skip("no native lib")
    q = native.BlockingQueue(capacity=1)
    assert q.pop(timeout_ms=50) is None  # empty: times out, no deadlock
    assert q.push(b"x")
    assert not q.push(b"y", timeout_ms=50)  # full: times out


def test_native_loader_multifile(tmp_path):
    if not native.available():
        pytest.skip("no native lib")
    paths = []
    expected = []
    for f in range(3):
        p = str(tmp_path / ("part-%d.recordio" % f))
        recs = [("f%d-r%d" % (f, i)).encode() for i in range(25)]
        _write_with(recordio._PyWriter, p, recs)
        expected.extend(recs)
        paths.append(p)
    loader = native.RecordIOLoader(paths, capacity=8, n_threads=3)
    got = list(loader)
    assert sorted(got) == sorted(expected)


@pytest.mark.parametrize("native_scanner", [True, False])
def test_recordio_corruption_detected(tmp_path, native_scanner):
    """Truncated/bit-flipped files raise IOError, never silent EOF."""
    if native_scanner and not native.available():
        pytest.skip("no native lib")
    path = str(tmp_path / "c.recordio")
    _write_with(recordio._PyWriter, path, [b"x" * 100 for _ in range(9)])
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF  # flip a payload bit
    open(path, "wb").write(bytes(blob))
    scls = recordio._NativeScanner if native_scanner else recordio._PyScanner
    with pytest.raises(IOError):
        list(scls(path))


def test_native_loader_missing_file(tmp_path):
    if not native.available():
        pytest.skip("no native lib")
    with pytest.raises(IOError):
        native.RecordIOLoader([str(tmp_path / "nope.recordio")])


def test_demo_trainer_cpp_binary(tmp_path):
    """train/demo_trainer.cc analog: build the CPython-embedding binary,
    export a tiny train program, and run the training loop from C++."""
    import os
    import shutil
    import subprocess
    import sys

    import sysconfig

    native_dir = os.path.join(os.path.dirname(fluid.__file__), "native")
    py_h = os.path.join(sysconfig.get_paths()["include"], "Python.h")
    if shutil.which("g++") is None or not os.path.exists(py_h):
        pytest.skip("no C++ toolchain / Python headers (%s)" % py_h)
    subprocess.run(["make", "demo_trainer"], cwd=native_dir, check=True,
                   capture_output=True)

    from paddle_tpu import layers
    from paddle_tpu.native.demo_driver import export_train_program

    img = layers.data("dt_img", shape=[16])
    label = layers.data("dt_label", shape=[1], dtype="int64")
    pred = layers.fc(layers.fc(img, 32, act="relu"), 4, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, label))
    fluid.optimizer.SGD(0.5).minimize(loss)
    export_train_program(
        str(tmp_path), fluid.default_main_program(),
        fluid.default_startup_program(),
        [{"name": "dt_img", "shape": [16], "dtype": "float32"},
         {"name": "dt_label", "shape": [1], "dtype": "int64", "max": 4}],
        [loss.name],
    )

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_TPU_ROOT"] = os.path.dirname(os.path.dirname(fluid.__file__))
    proc = subprocess.run(
        [os.path.join(native_dir, "demo_trainer"), str(tmp_path), "8", "16"],
        env=env, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "improved=true" in proc.stdout, proc.stdout


def test_c_inference_abi(tmp_path):
    """inference C ABI (paddle_fluid C API analog): build the .so + demo,
    export a model, run it from C, and match Python's outputs."""
    import os
    import shutil
    import subprocess
    import sysconfig

    native_dir = os.path.join(os.path.dirname(fluid.__file__), "native")
    py_h = os.path.join(sysconfig.get_paths()["include"], "Python.h")
    if shutil.which("g++") is None or not os.path.exists(py_h):
        pytest.skip("no C++ toolchain / Python headers")
    subprocess.run(["make", "capi_demo"], cwd=native_dir, check=True,
                   capture_output=True)

    from paddle_tpu import layers

    x = layers.data("cax", shape=[8])
    pred = layers.fc(layers.fc(x, 16, act="relu"), 4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    model_dir = str(tmp_path / "capi_model")
    fluid.save_inference_model(model_dir, ["cax"], [pred], exe)
    (ref,) = exe.run(
        program=fluid.default_main_program().clone(for_test=True),
        feed={"cax": np.ones((2, 8), "float32")}, fetch_list=[pred],
    )

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [os.path.join(native_dir, "capi_demo"),
         os.path.dirname(os.path.dirname(fluid.__file__)),
         model_dir, "cax", "2", "2", "8"],
        env=env, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "CAPI_OK" in proc.stdout
    line = [l for l in proc.stdout.splitlines() if "first=" in l][0]
    got = [float(v) for v in
           line.split("first=[")[1].rstrip("]").split(",")]
    np.testing.assert_allclose(got, np.asarray(ref)[0][:4], rtol=1e-4)


def test_trainer_cli_trains_checkpoints_and_resumes(tmp_path):
    """paddle_trainer-binary capability (TrainerMain.cpp / `paddle train`):
    the CLI trains an exported program dir, writes serial checkpoints,
    resumes from them, and saves persistables; rc=0 iff loss improved."""
    import os
    import subprocess
    import sys

    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.native.demo_driver import export_train_program

    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x = layers.data("x", shape=[8])
        label = layers.data("label", shape=[1], dtype="int64")
        pred = layers.fc(x, 4, act="softmax")
        loss = layers.mean(layers.cross_entropy(pred, label))
        fluid.optimizer.SGD(0.5).minimize(loss)
    d = str(tmp_path / "prog")
    export_train_program(
        d, main, startup,
        [{"name": "x", "shape": [8], "dtype": "float32"},
         {"name": "label", "shape": [1], "dtype": "int64", "max": 4}],
        [loss.name])

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ck = str(tmp_path / "ck")
    out_dir = str(tmp_path / "params")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.trainer_cli", "--program_dir", d,
         "--steps", "6", "--checkpoint_dir", ck, "--checkpoint_every", "3",
         "--save_dir", out_dir, "--log_every", "2"],
        cwd="/root/repo", env=env, timeout=300,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    text = r.stdout.decode()
    assert r.returncode == 0, text
    assert "first loss" in text and os.path.isdir(out_dir), text
    serials = [p for p in os.listdir(ck) if p.startswith("checkpoint_")]
    assert serials, os.listdir(ck)

    # resume: the saved step counter short-circuits already-done steps
    r2 = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.trainer_cli", "--program_dir", d,
         "--steps", "6", "--checkpoint_dir", ck],
        cwd="/root/repo", env=env, timeout=300,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    text2 = r2.stdout.decode()
    assert r2.returncode == 0, text2
    assert "resumed from checkpoint at step 6" in text2, text2
    assert "nothing to do" in text2, text2
