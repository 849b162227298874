"""rms_norm: x * rsqrt(mean(x^2) + eps) * w over the last axis, statistics
in f32 whatever X's dtype, output in X's dtype; its infer rule; its grad."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, layers
from paddle_tpu.analysis.infer import InferError, VarInfo, get_infer_rule

from op_test import OpTest, run_single_op


def _rms(x, w, eps):
    x64 = x.astype("float64")
    return (x64 / np.sqrt((x64 * x64).mean(-1, keepdims=True) + eps)
            * w.astype("float64"))


class _RMSNormCase(OpTest):
    op_type = "rms_norm"

    def __init__(self, shape, eps):
        self.shape, self.eps = shape, eps

    def setup(self):
        rng = np.random.RandomState(3)
        x = rng.randn(*self.shape).astype("float32")
        w = rng.uniform(0.5, 1.5, self.shape[-1:]).astype("float32")
        self.inputs = {"X": x, "Scale": w}
        self.attrs = {"epsilon": self.eps}
        self.outputs = {"Y": _rms(x, w, self.eps).astype("float32")}


SHAPES = [((6, 16), 1e-5), ((2, 5, 8), 1e-5), ((3, 4), 1e-2)]


@pytest.mark.parametrize("shape, eps", SHAPES)
def test_output_matches_the_float64_formula(shape, eps):
    _RMSNormCase(shape, eps).check_output(atol=1e-6, rtol=1e-5)


@pytest.mark.parametrize("wrt", ["x", "scale"])
def test_gradient_matches_central_differences(wrt):
    _RMSNormCase((3, 4), 1e-5).check_grad([wrt], "Y",
                                          max_relative_error=1e-2)


def test_bf16_rows_keep_f32_statistics_and_leave_in_bf16():
    """The op is dtype-transparent for the AMP trunk pass: a bf16 X gives
    a bf16 Y, and Y is the f32 formula of the bf16 values rounded once
    (not a bf16 mean of bf16 squares, which loses 2-3 digits over 256
    elements)."""
    import jax.numpy as jnp

    rng = np.random.RandomState(5)
    x = np.asarray(jnp.asarray(rng.randn(4, 256) * 3.0, jnp.bfloat16))
    w = np.ones((256,), "float32")
    (y,) = run_single_op("rms_norm", {"X": x, "Scale": w},
                         {"epsilon": 1e-5}, ["Y"])
    assert y.dtype == jnp.bfloat16
    want = _rms(x.astype("float32"), w, 1e-5)
    np.testing.assert_allclose(y.astype("float64"), want, rtol=2 ** -8)


def test_infer_rule_keeps_shape_and_dtype_and_checks_the_scale():
    rule = get_infer_rule("rms_norm")

    class Op:
        attrs = {"epsilon": 1e-5}

    out = rule.fn(Op, {"X": [VarInfo((-1, 16, 64), "bfloat16")],
                       "Scale": [VarInfo((64,), "float32")]})
    assert out["Y"][0].shape == (-1, 16, 64)
    assert out["Y"][0].dtype == "bfloat16"
    with pytest.raises(InferError, match="does not match"):
        rule.fn(Op, {"X": [VarInfo((4, 64), "float32")],
                     "Scale": [VarInfo((32,), "float32")]})


def test_layer_creates_a_unit_scale_and_the_program_verifies():
    x = layers.data("x", shape=[8, 32])
    y = layers.rms_norm(x, epsilon=1e-5)
    main = fluid.default_main_program()
    assert not [d for d in analysis.verify_program(main, fetches=[y])
                if d.is_error]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    (w,) = [p for p in main.global_block().all_parameters()]
    assert np.all(np.asarray(fluid.global_scope().find_var(w.name)) == 1.0)
    xv = np.random.RandomState(0).randn(2, 8, 32).astype("float32")
    (got,) = exe.run(main, feed={"x": xv}, fetch_list=[y])
    np.testing.assert_allclose(got, _rms(xv, np.ones(32), 1e-5), rtol=1e-5,
                               atol=1e-6)


def test_a_gain_a_group_covers_the_last_two_axes():
    """`gain_axes=2` (Mamba-2's norm over groups of d_inner / n_groups
    channels, each group with gains of its own): the parameter is
    [groups, d], the statistic stays the last axis's, result and both
    gradients are the formula's; the infer rule takes a Scale that matches
    X's last axes and refuses one that does not."""
    import jax

    from paddle_tpu import framework, unique_name
    from paddle_tpu.initializer import NumpyArrayInitializer
    from paddle_tpu.param_attr import ParamAttr

    rng = np.random.RandomState(3)
    xv = rng.randn(2, 5, 4, 16).astype("float32")
    gain = rng.uniform(0.5, 1.5, (4, 16)).astype("float32")
    mixv = rng.uniform(0.5, 1.5, xv.shape).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("x", shape=list(xv.shape), append_batch_size=False)
        x.stop_gradient = False
        mix = layers.data("mix", shape=list(xv.shape),
                          append_batch_size=False)
        y = layers.rms_norm(x, 1e-5, gain_axes=2, param_attr=ParamAttr(
            name="gain", initializer=NumpyArrayInitializer(gain)))
        loss = layers.reduce_sum(layers.elementwise_mul(y, mix))
        fluid.backward.append_backward(loss)
    assert tuple(main.global_block().var("gain").shape) == (4, 16)
    assert not [d for d in analysis.verify_program(main, fetches=[loss])
                if d.is_error]
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        got = exe.run(main, feed={"x": xv, "mix": mixv}, fetch_list=[
            y, main._grad_names["x"], main._grad_names["gain"]])

    def plain(a, w):
        return a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True)
                                 + 1e-5) * w

    want = (plain(xv, gain),) + jax.grad(
        lambda a, w: (plain(a, w) * mixv).sum(), argnums=(0, 1))(
            jnp.asarray(xv), jnp.asarray(gain))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    rule = get_infer_rule("rms_norm")

    class Op:
        attrs = {"epsilon": 1e-5}

    x_info = [VarInfo((-1, 5, 4, 16), "bfloat16")]
    assert rule.fn(Op, {"X": x_info, "Scale": [VarInfo(
        (4, 16), "float32")]})["Y"][0].shape == (-1, 5, 4, 16)
    for wrong in ((8, 16), (5, 4, 16, 1), (2, 5, 4, 16)):
        with pytest.raises(InferError, match="does not match"):
            rule.fn(Op, {"X": [VarInfo((2, 5, 4, 16), "float32")],
                         "Scale": [VarInfo(wrong, "float32")]})
