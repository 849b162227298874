"""Per-op checks for nn ops (conv/pool/norm/dropout/rnn) — the mirror of the
reference's test_conv2d_op.py / test_pool2d_op.py / test_batch_norm_op.py
numpy-reference contract."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from op_test import OpTest, run_single_op

rng = np.random.RandomState(7)


def ref_conv2d(x, w, stride, pad):
    n, c, h, wd = x.shape
    oc, ic, kh, kw = w.shape
    xp = np.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (wd + 2 * pad - kw) // stride + 1
    out = np.zeros((n, oc, oh, ow), dtype="float64")
    for i in range(oh):
        for j in range(ow):
            patch = xp[:, :, i * stride : i * stride + kh, j * stride : j * stride + kw]
            out[:, :, i, j] = np.einsum("ncij,ocij->no", patch, w)
    return out


class TestConv2d(OpTest):
    def setup(self):
        self.op_type = "conv2d"
        x = rng.rand(2, 3, 7, 7).astype("float32")
        w = rng.rand(4, 3, 3, 3).astype("float32") - 0.5
        self.inputs = {"Input": x, "Filter": w}
        self.attrs = {"strides": [2, 2], "paddings": [1, 1], "dilations": [1, 1], "groups": 1}
        self.outputs = {"Output": ref_conv2d(x, w, 2, 1).astype("float32")}

    def test(self):
        self.check_output(atol=1e-4)
        self.check_grad(["input", "filter"], "Output", max_relative_error=5e-2)


class TestDepthwiseConv(OpTest):
    def setup(self):
        self.op_type = "depthwise_conv2d"
        x = rng.rand(1, 3, 5, 5).astype("float32")
        w = rng.rand(3, 1, 3, 3).astype("float32")
        ref = np.zeros((1, 3, 3, 3), "float64")
        for ch in range(3):
            ref[:, ch : ch + 1] = ref_conv2d(
                x[:, ch : ch + 1], w[ch : ch + 1], 1, 0
            )
        self.inputs = {"Input": x, "Filter": w}
        self.attrs = {"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1]}
        self.outputs = {"Output": ref.astype("float32")}

    def test(self):
        self.check_output(atol=1e-4)


class TestPool2dMax(OpTest):
    def setup(self):
        self.op_type = "pool2d"
        # well-separated values: finite differences near argmax ties split
        # gradient credit, so keep a > 2*delta gap between any two entries
        vals = np.arange(2 * 3 * 6 * 6, dtype="float32") * 0.05
        x = vals[rng.permutation(vals.size)].reshape(2, 3, 6, 6)
        out = x.reshape(2, 3, 3, 2, 3, 2).max(axis=(3, 5))
        self.inputs = {"X": x}
        self.attrs = {"pooling_type": "max", "ksize": [2, 2], "strides": [2, 2], "paddings": [0, 0]}
        self.outputs = {"Out": out}

    def test(self):
        self.check_output()
        self.check_grad(["x"], "Out", max_relative_error=2e-2)


class TestPool2dAvg(OpTest):
    def setup(self):
        self.op_type = "pool2d"
        x = rng.rand(2, 3, 6, 6).astype("float32")
        out = x.reshape(2, 3, 3, 2, 3, 2).mean(axis=(3, 5))
        self.inputs = {"X": x}
        self.attrs = {"pooling_type": "avg", "ksize": [2, 2], "strides": [2, 2], "paddings": [0, 0]}
        self.outputs = {"Out": out}

    def test(self):
        self.check_output()
        self.check_grad(["x"], "Out")


class TestPool2dGlobal(OpTest):
    def setup(self):
        self.op_type = "pool2d"
        x = rng.rand(2, 3, 5, 5).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"pooling_type": "avg", "ksize": [1, 1], "global_pooling": True}
        self.outputs = {"Out": x.mean(axis=(2, 3), keepdims=True)}

    def test(self):
        self.check_output()


class TestBatchNormInference(OpTest):
    def setup(self):
        self.op_type = "batch_norm"
        x = rng.rand(2, 4, 3, 3).astype("float32")
        scale = rng.rand(4).astype("float32")
        bias = rng.rand(4).astype("float32")
        mean = rng.rand(4).astype("float32")
        var = rng.rand(4).astype("float32") + 0.5
        y = (x - mean.reshape(1, 4, 1, 1)) / np.sqrt(
            var.reshape(1, 4, 1, 1) + 1e-5
        ) * scale.reshape(1, 4, 1, 1) + bias.reshape(1, 4, 1, 1)
        self.inputs = {"X": x, "Scale": scale, "Bias": bias, "Mean": mean, "Variance": var}
        self.attrs = {"is_test": True, "epsilon": 1e-5}
        self.outputs = {"Y": y}

    def test(self):
        self.check_output(atol=1e-4, no_check_set={"MeanOut", "VarianceOut", "SavedMean", "SavedVariance"})


class TestBatchNormTraining(OpTest):
    def setup(self):
        self.op_type = "batch_norm"
        x = rng.rand(4, 3, 2, 2).astype("float32")
        scale = np.ones(3, "float32")
        bias = np.zeros(3, "float32")
        mean = np.zeros(3, "float32")
        var = np.ones(3, "float32")
        bm = x.mean(axis=(0, 2, 3))
        bv = x.var(axis=(0, 2, 3))
        y = (x - bm.reshape(1, 3, 1, 1)) / np.sqrt(bv.reshape(1, 3, 1, 1) + 1e-5)
        self.inputs = {"X": x, "Scale": scale, "Bias": bias, "Mean": mean, "Variance": var}
        self.attrs = {"is_test": False, "epsilon": 1e-5, "momentum": 0.9}
        self.outputs = {
            "Y": y,
            "MeanOut": 0.9 * mean + 0.1 * bm,
            "VarianceOut": 0.9 * var + 0.1 * bv,
        }

    def test(self):
        self.check_output(atol=1e-4, no_check_set={"SavedMean", "SavedVariance"})


class TestLayerNormNoAffine(OpTest):
    def setup(self):
        self.op_type = "layer_norm"
        x = rng.rand(3, 8).astype("float32")
        mean = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        self.inputs = {"X": x}
        self.attrs = {"begin_norm_axis": 1}
        self.outputs = {"Y": (x - mean) / np.sqrt(var + 1e-5)}

    def test(self):
        self.check_output(atol=1e-4, no_check_set={"Mean", "Variance"})


def test_dropout_statistics():
    x = layers.data("x", shape=[1000], append_batch_size=False)
    out = layers.dropout(x, dropout_prob=0.3, dropout_implementation="upscale_in_train")
    exe = fluid.Executor(fluid.CPUPlace())
    xv = np.ones(1000, "float32")
    (r,) = exe.run(feed={"x": xv}, fetch_list=[out])
    kept = (np.asarray(r) > 0).mean()
    assert 0.6 < kept < 0.8, kept
    # upscale: mean preserved
    assert 0.85 < np.asarray(r).mean() < 1.15
    # different step -> different mask
    (r2,) = exe.run(feed={"x": xv}, fetch_list=[out])
    assert not np.array_equal(np.asarray(r), np.asarray(r2))


def test_dropout_is_test_identity():
    x = layers.data("x", shape=[50], append_batch_size=False)
    out = layers.dropout(x, dropout_prob=0.3, is_test=True,
                         dropout_implementation="upscale_in_train")
    exe = fluid.Executor(fluid.CPUPlace())
    xv = rng.rand(50).astype("float32")
    (r,) = exe.run(feed={"x": xv}, fetch_list=[out])
    np.testing.assert_allclose(np.asarray(r), xv, rtol=1e-6)


def test_lstm_layer_trains():
    """scan-backed lstm: forward shape + gradient flows end-to-end."""
    x = layers.data("x", shape=[6, 32])  # [B, T, C]
    proj = layers.fc(x, size=4 * 16, num_flatten_dims=2)
    hidden, last_c = layers.dynamic_lstm(proj, size=4 * 16)
    pool = layers.reduce_mean(hidden, dim=[1])
    pred = layers.fc(pool, size=2, act="softmax")
    label = layers.data("label", shape=[1], dtype="int64")
    loss = layers.mean(layers.cross_entropy(pred, label))
    fluid.optimizer.Adam(0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = rng.rand(8, 6, 32).astype("float32")
    yv = rng.randint(0, 2, (8, 1)).astype("int64")
    losses = []
    for _ in range(10):
        (lv,) = exe.run(feed={"x": xv, "label": yv}, fetch_list=[loss])
        losses.append(float(np.asarray(lv)[0]))
    assert losses[-1] < losses[0], losses


def test_gru_layer_forward():
    x = layers.data("x", shape=[5, 24])
    proj = layers.fc(x, size=3 * 8, num_flatten_dims=2)
    hidden = layers.dynamic_gru(proj, size=8)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = rng.rand(4, 5, 24).astype("float32")
    (h,) = exe.run(feed={"x": xv}, fetch_list=[hidden])
    assert np.asarray(h).shape == (4, 5, 8)
    assert np.isfinite(np.asarray(h)).all()


def _sigmoid(v):
    return 1.0 / (1.0 + np.exp(-v))


def _ref_recurrence(kind, x, w, h0, c0, lens, reverse):
    """Per-row numpy loops over the valid steps only: lstm gates
    i|f|c_hat|o (lstm_op.cc), gru [update|reset|candidate] with
    h = u*c + (1-u)*h_prev (gru_kernel.h).  A step at or past a row's
    length repeats the state the row holds there."""
    bsz, t, _ = x.shape
    hid = h0.shape[1]
    hs, cs = np.zeros((bsz, t, hid)), np.zeros((bsz, t, hid))
    for b in range(bsz):
        h, c = h0[b].astype("float64"), c0[b].astype("float64")
        for step in (range(t - 1, -1, -1) if reverse else range(t)):
            if step < lens[b]:
                if kind == "lstm":
                    i, f, g, o = np.split(x[b, step] + h @ w, 4)
                    c = _sigmoid(f) * c + _sigmoid(i) * np.tanh(g)
                    h = _sigmoid(o) * np.tanh(c)
                else:
                    u, r = np.split(_sigmoid(
                        x[b, step, :2 * hid] + h @ w[:, :2 * hid]), 2)
                    cand = np.tanh(x[b, step, 2 * hid:]
                                   + (r * h) @ w[:, 2 * hid:])
                    h = u * cand + (1 - u) * h
            hs[b, step], cs[b, step] = h, c
    last = 0 if reverse else t - 1
    return hs, cs, hs[:, last], cs[:, last]


@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("kind", ["lstm", "gru"])
def test_padded_recurrence_matches_numpy(kind, reverse):
    """padded_lstm / padded_gru (the one lax.scan lowering each op has)
    against numpy loops, in both directions, with initial state, a bias
    (lstm), and rows shorter than the batch's time axis, one of them
    empty: every step's hidden (and cell) state and the last ones."""
    bsz, t, hid = 4, 6, 5
    n = 4 if kind == "lstm" else 3
    r = np.random.RandomState(11)
    x = r.randn(bsz, t, n * hid).astype("float32")
    w = (r.randn(hid, n * hid) * 0.4).astype("float32")
    h0 = r.randn(bsz, hid).astype("float32")
    c0 = r.randn(bsz, hid).astype("float32")
    lens = np.array([6, 3, 0, 1], "int32")
    ins = {"Input": x, "Weight": w, "H0": h0, "SeqLen": lens}
    if kind == "lstm":
        bias = (r.randn(n * hid) * 0.2).astype("float32")
        ins.update(C0=c0, Bias=bias)
        slots = ["Hidden", "CellSeq", "LastH", "LastC"]
        want = _ref_recurrence(kind, x + bias, w, h0, c0, lens, reverse)
    else:
        slots = ["Hidden", "LastH"]
        want = _ref_recurrence(kind, x, w, h0, c0, lens, reverse)[0::2]
    got = run_single_op("padded_" + kind, ins, {"is_reverse": reverse}, slots)
    for slot, a, b in zip(slots, got, want):
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-5, atol=1e-5,
                                   err_msg=slot)
