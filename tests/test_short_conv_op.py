"""short_conv: the gated short convolution between an LFM2 conv layer's two
projections, Out = C * causal_depthwise_conv(B * u) with [B, C, u] the
thirds of BCX's last axis.  Against a plain statement of the same thing
(L shifted adds over numpy / jax.numpy arrays): forward, causality,
both gradients, the dtypes under bf16, and the infer rule."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, framework, layers, unique_name
from paddle_tpu.analysis.infer import InferError, VarInfo, get_infer_rule
from paddle_tpu.initializer import NumpyArrayInitializer
from paddle_tpu.ops.nn_ops import gated_short_conv
from paddle_tpu.param_attr import ParamAttr

B, T, D = 3, 10, 8


def plain(bcx, filt):
    """v_t = sum_j filt[:, j] * (B u)_{t - (L-1) + j}, zeros left of t=0;
    works on numpy and on jax arrays, over the second to last axis."""
    d, taps = filt.shape
    t = bcx.shape[-2]
    b, c, u = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    bu = b * u
    v = 0.0 * bu
    for j in range(taps):
        back = min(taps - 1 - j, t)
        shifted = jnp.concatenate(
            [jnp.zeros_like(bu[..., :back, :]), bu[..., :t - back, :]], -2)
        v = v + shifted * filt[:, j]
    return c * v


def _data(taps, rank):
    rng = np.random.RandomState(3 + taps)
    shape = (B, T, 3 * D) if rank == 3 else (T, 3 * D)
    return {"bcx": rng.randn(*shape).astype("float32"),
            "filt": rng.randn(D, taps).astype("float32"),
            "mix": rng.uniform(0.5, 1.5, shape[:-1] + (D,)).astype("float32")}


@functools.lru_cache(maxsize=None)
def _run(taps, rank):
    """(Out, dBCX, dFilter, verify errors) of one program, and the plain
    form's."""
    w = _data(taps, rank)
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("bcx", shape=list(w["bcx"].shape),
                        append_batch_size=False)
        x.stop_gradient = False
        mix = layers.data("mix", shape=list(w["mix"].shape),
                          append_batch_size=False)
        y = layers.short_conv(x, taps, param_attr=ParamAttr(
            name="filt", initializer=NumpyArrayInitializer(w["filt"])))
        loss = layers.reduce_sum(layers.elementwise_mul(y, mix))
        fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        out = exe.run(main, feed={"bcx": w["bcx"], "mix": w["mix"]},
                      fetch_list=[y, main._grad_names["bcx"],
                                  main._grad_names["filt"]])
    errors = [d for d in analysis.verify_program(main, fetches=[loss])
              if d.is_error]
    want = plain(jnp.asarray(w["bcx"]), jnp.asarray(w["filt"]))
    grads = jax.grad(lambda a, k: (plain(a, k) * w["mix"]).sum(),
                     argnums=(0, 1))(jnp.asarray(w["bcx"]),
                                     jnp.asarray(w["filt"]))
    return out, errors, (want,) + grads, y


CASES = [(3, 3), (3, 2), (4, 3), (1, 3)]


@pytest.mark.parametrize("taps, rank", CASES)
def test_forward_is_the_shifted_adds(taps, rank):
    got, errors, want, y = _run(taps, rank)
    assert got[0].shape == want[0].shape == tuple(y.shape)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=1e-6)
    assert not errors


@pytest.mark.parametrize("wrt", ["BCX", "Filter"])
@pytest.mark.parametrize("taps, rank", CASES)
def test_gradient_is_jax_grad_of_the_shifted_adds(taps, rank, wrt):
    got, _, want, _ = _run(taps, rank)
    i = 1 if wrt == "BCX" else 2
    assert got[i].shape == want[i].shape
    np.testing.assert_allclose(got[i], want[i], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cut", [0, 4, T - 2])
def test_output_at_t_does_not_see_inputs_after_t(cut):
    """Causal: changing every input after position `cut` leaves the output
    up to and including `cut` bit for bit what it was, and moves the
    output right after it."""
    w = _data(3, 3)
    later = w["bcx"].copy()
    later[:, cut + 1:] += 7.0
    a = np.asarray(gated_short_conv(jnp.asarray(w["bcx"]),
                                    jnp.asarray(w["filt"])))
    b = np.asarray(gated_short_conv(jnp.asarray(later),
                                    jnp.asarray(w["filt"])))
    np.testing.assert_array_equal(a[:, :cut + 1], b[:, :cut + 1])
    assert np.abs(a[:, cut + 1] - b[:, cut + 1]).max() > 1.0


def test_a_sequence_shorter_than_the_filter_is_legal():
    w = _data(4, 3)
    short = jnp.asarray(w["bcx"][:, :2])
    np.testing.assert_allclose(
        gated_short_conv(short, jnp.asarray(w["filt"])),
        plain(short, jnp.asarray(w["filt"])), rtol=1e-6, atol=1e-6)


def test_bf16_operands_float32_arithmetic():
    """bf16 in, bf16 out, and in between the float32 arithmetic of the
    bf16 values rounded once at the end: not what bf16 arithmetic gives."""
    w = _data(3, 3)
    x16 = jnp.asarray(w["bcx"]).astype(jnp.bfloat16)
    filt = jnp.asarray(w["filt"])
    got = gated_short_conv(x16, filt)
    assert got.dtype == jnp.bfloat16
    want = plain(x16.astype(jnp.float32), filt).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got, "float32"),
                                  np.asarray(want, "float32"))
    in_bf16 = plain(x16, filt.astype(jnp.bfloat16))
    assert np.abs(np.asarray(in_bf16, "float32")
                  - np.asarray(want, "float32")).max() > 0


def test_amp_pass_runs_the_op_on_bf16_activations_with_a_float32_filter():
    """Between two projections under the AMP pass the op reads the first
    one's bf16 result and hands a bf16 result to the second (dtype-
    transparent like rms_norm); the filter stays f32."""
    from paddle_tpu.transpiler.pass_registry import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("x", shape=[T, D], dtype="float32")
        bcx = layers.fc(x, size=3 * D, num_flatten_dims=2, bias_attr=False)
        y = layers.short_conv(bcx, 3)
        out = layers.fc(y, size=D, num_flatten_dims=2, bias_attr=False)
        apply_pass(main, "bf16_amp_pass")
    block = main.global_block()
    (op,) = [o for o in block.ops if o.type == "short_conv"]
    dtypes = {slot: str(block.var(names[0]).dtype)
              for slot, names in list(op.inputs.items())
              + list(op.outputs.items())}
    assert dtypes == {"BCX": "bfloat16", "Filter": "float32",
                      "Out": "bfloat16"}
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        (got,) = exe.run(main, feed={"x": np.ones((2, T, D), "float32")},
                         fetch_list=[out])
    assert got.dtype == np.float32 and np.isfinite(got).all()


def _infer(bcx, filt, dtype="bfloat16"):
    class Op:
        attrs = {}

    return get_infer_rule("short_conv").fn(Op, {
        "BCX": [VarInfo(bcx, dtype)], "Filter": [VarInfo(filt, "float32")]})


def test_infer_rule_gives_a_third_of_the_last_axis_in_bcxs_dtype():
    out = _infer((-1, 32, 3 * D), (D, 3))["Out"][0]
    assert out.shape == (-1, 32, D) and out.dtype == "bfloat16"


@pytest.mark.parametrize("bcx, filt", [((4, 32, 3 * D), (D + 1, 3)),
                                       ((4, 32, 3 * D), (D, 3, 1)),
                                       ((3 * D,), (D, 3))])
def test_infer_rule_refuses_inconsistent_edges(bcx, filt):
    with pytest.raises(InferError, match="short_conv"):
        _infer(bcx, filt)


def test_program_flops_counts_the_elementwise_work():
    from paddle_tpu.utils.flops import program_flops

    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("bcx", shape=[B, T, 3 * D], append_batch_size=False)
        layers.short_conv(x, 3)
    assert program_flops(main) == (2 * 3 + 2) * B * T * D
