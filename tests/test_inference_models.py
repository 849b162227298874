"""Real-model inference analyzer tests (analyzer_*_tester.cc role).

The reference validates its inference stack on REAL models with
accuracy + latency checks (inference/tests/api/analyzer_resnet50_tester.cc:25,
analyzer_rnn1_tester.cc): train → save → load through the analysis
pipeline with every fusion pass on → compare against the training-mode
forward and record latency.  Here the same cycle runs on the in-repo
ResNet-50 (models/resnet.py) and Transformer encoder
(models/transformer.py), one leg routed through the C inference ABI
(native/capi.cc), on small shapes so the cycle fits the CPU suite.
"""

import os
import shutil
import subprocess
import sysconfig
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor


def _latency_ms(fn, warmup=1, iters=5):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e3


def _op_types(program):
    return [op.type for op in program.global_block().ops]


@pytest.mark.slow  # full-train/full-model integration pass (tens of seconds on this 2-core sandbox); rides scripts/ci.sh --full — the fast lane must finish inside tier-1's time budget
def test_analyzer_resnet50(tmp_path, capsys):
    """analyzer_resnet50_tester.cc:25 cycle on the in-repo ResNet-50:
    2 train steps → save_inference_model → AnalysisConfig (conv+bn fold
    et al on) → output parity vs the training program's for_test clone
    + a latency record."""
    from paddle_tpu.models.resnet import resnet_imagenet

    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        img = layers.data("image", shape=[3, 32, 32])
        label = layers.data("label", shape=[1], dtype="int64")
        predict = resnet_imagenet(img, class_dim=10, depth=50)
        loss = layers.mean(layers.cross_entropy(predict, label))
        test_prog = main.clone(for_test=True)
        fluid.optimizer.Momentum(0.01, momentum=0.9).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    x = rng.rand(2, 3, 32, 32).astype("float32")
    y = rng.randint(0, 10, (2, 1)).astype("int64")
    for _ in range(2):
        exe.run(main, feed={"image": x, "label": y}, fetch_list=[loss])

    model_dir = str(tmp_path / "resnet50")
    fluid.save_inference_model(model_dir, ["image"], [predict], exe,
                               main_program=main)
    (ref,) = exe.run(test_prog, feed={"image": x}, fetch_list=[predict])

    predictor = create_paddle_predictor(AnalysisConfig(model_dir))
    types = _op_types(predictor.program)
    # conv_bn_fuse_pass folded every inference-mode batch_norm
    assert "batch_norm" not in types, types
    (out,) = predictor.run({"image": x})
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-3, atol=1e-5)

    ms = _latency_ms(lambda: predictor.run({"image": x}))
    with capsys.disabled():
        print("\n[analyzer] resnet50 bs2/32px cpu latency %.1f ms/batch "
              "(%d fused ops vs %d trained)" %
              (ms, len(types), len(_op_types(test_prog))))
    assert ms > 0


@pytest.mark.slow  # full-train/full-model integration pass (tens of seconds on this 2-core sandbox); rides scripts/ci.sh --full — the fast lane must finish inside tier-1's time budget
def test_analyzer_resnet50_c_abi(tmp_path):
    """The same saved ResNet-50 served from C through the inference ABI
    (inference/capi demo_ci role): outputs must match the Python
    AnalysisConfig predictor on the identical feed."""
    from paddle_tpu.models.resnet import resnet_imagenet

    native_dir = os.path.join(os.path.dirname(fluid.__file__), "native")
    py_h = os.path.join(sysconfig.get_paths()["include"], "Python.h")
    if (shutil.which("g++") is None or shutil.which("make") is None
            or not os.path.exists(py_h)):
        pytest.skip("no C++ toolchain / Python headers")
    subprocess.run(["make", "capi_demo"], cwd=native_dir, check=True,
                   capture_output=True)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        img = layers.data("image", shape=[3, 16, 16])
        predict = resnet_imagenet(img, class_dim=4, depth=50, is_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    model_dir = str(tmp_path / "resnet50_capi")
    fluid.save_inference_model(model_dir, ["image"], [predict], exe,
                               main_program=main, scope=scope)

    x = np.ones((1, 3, 16, 16), "float32")
    predictor = create_paddle_predictor(AnalysisConfig(model_dir))
    (ref,) = predictor.run({"image": x})

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [os.path.join(native_dir, "capi_demo"),
         os.path.dirname(os.path.dirname(fluid.__file__)),
         model_dir, "image", "4", "1", "3", "16", "16"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "CAPI_OK" in proc.stdout
    line = [l for l in proc.stdout.splitlines() if "first=" in l][0]
    got = [float(v) for v in line.split("first=[")[1].rstrip("]").split(",")]
    np.testing.assert_allclose(got, np.asarray(ref)[0][:4], rtol=1e-3,
                               atol=1e-5)


@pytest.mark.slow  # full-train/full-model integration pass (tens of seconds on this 2-core sandbox); rides scripts/ci.sh --full — the fast lane must finish inside tier-1's time budget
def test_analyzer_transformer_encoder(tmp_path, capsys):
    """Transformer-encoder analyzer cycle (analyzer_* role for the
    attention stack): train a 2-layer encoder classifier, save, load via
    AnalysisConfig — attention_fuse_pass must collapse each encoder
    layer's attention into ONE fused_attention op — and match the
    training program's for_test clone, with a latency record."""
    from paddle_tpu.models.transformer import (
        ModelHyperParams,
        encoder_layer,
        prepare_embedding,
    )

    class TinyHP(ModelHyperParams):
        src_vocab_size = 128
        max_length = 32
        d_model = 32
        d_inner_hid = 64
        n_head = 4
        n_layer = 2
        dropout = 0.1

    T = 16
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        with fluid.unique_name.guard():
            ids = layers.data("src_ids", shape=[T], dtype="int64")
            # rank-1 key-padding bias [B, 1, 1, Tk] — the fusable mask
            # pattern (attention_fuse_pass leaves dense [B,1,Tq,Tk] alone)
            bias = layers.data("src_bias", shape=[1, 1, T])
            label = layers.data("label", shape=[1], dtype="int64")
            x = prepare_embedding(
                ids, TinyHP.src_vocab_size, TinyHP.d_model, TinyHP.max_length,
                TinyHP.dropout, "src_pos_enc_table")
            for _ in range(TinyHP.n_layer):
                x = encoder_layer(x, bias, TinyHP)
            pooled = layers.reduce_mean(x, dim=1)
            pred = layers.fc(pooled, size=4, act="softmax")
            loss = layers.mean(layers.cross_entropy(pred, label))
            test_prog = main.clone(for_test=True)
            fluid.optimizer.Adam(1e-3).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(1)
    ids_np = rng.randint(1, TinyHP.src_vocab_size, (4, T)).astype("int64")
    bias_np = np.zeros((4, 1, 1, T), "float32")
    bias_np[:, :, :, -2:] = -1e9  # pad out the last two key slots
    label_np = rng.randint(0, 4, (4, 1)).astype("int64")
    for _ in range(3):
        exe.run(main, feed={"src_ids": ids_np, "src_bias": bias_np,
                            "label": label_np}, fetch_list=[loss])

    model_dir = str(tmp_path / "tfm_encoder")
    fluid.save_inference_model(model_dir, ["src_ids", "src_bias"], [pred],
                               exe, main_program=main)
    (ref,) = exe.run(test_prog, feed={"src_ids": ids_np,
                                      "src_bias": bias_np},
                     fetch_list=[pred])

    predictor = create_paddle_predictor(AnalysisConfig(model_dir))
    types = _op_types(predictor.program)
    assert types.count("fused_attention") == TinyHP.n_layer, types
    (out,) = predictor.run({"src_ids": ids_np, "src_bias": bias_np})
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-4, atol=1e-6)

    ms = _latency_ms(
        lambda: predictor.run({"src_ids": ids_np, "src_bias": bias_np}))
    with capsys.disabled():
        print("\n[analyzer] transformer-encoder bs4/T16 cpu latency "
              "%.1f ms/batch (fused_attention x%d)" %
              (ms, types.count("fused_attention")))
    assert ms > 0
