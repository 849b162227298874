"""The ops that had a second, flag-chosen lowering until PR 44 (`fc`,
`fused_swiglu`, `fused_residual_ln`, `layer_norm`,
`softmax_with_cross_entropy`, the QStart forms of `fused_attention`),
each run through its registered lowering (`get_op(type).lower`) against
numpy: the shapes, dtypes and activations of the cases that used to
compare a Mosaic kernel with this dense form, the reference now
independent of both, gradients (jax.vjp over the lowering, which is what
a grad op runs) against the closed forms written out below."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import flags
from paddle_tpu.core.registry import LowerCtx, get_op

_erf = np.vectorize(math.erf, otypes=[np.float64])


def _sigmoid(z):
    return 1.0 / (1.0 + np.exp(-z))


# activation -> (f, f') in float64; the entries of nn_ops._mm_act
ACTS = {
    "": (lambda z: z, np.ones_like),
    "identity": (lambda z: z, np.ones_like),
    "relu": (lambda z: np.maximum(z, 0.0), lambda z: (z > 0) * 1.0),
    "tanh": (np.tanh, lambda z: 1.0 - np.tanh(z) ** 2),
    "sigmoid": (_sigmoid, lambda z: _sigmoid(z) * (1.0 - _sigmoid(z))),
    "gelu": (lambda z: 0.5 * z * (1.0 + _erf(z / math.sqrt(2.0))),
             lambda z: 0.5 * (1.0 + _erf(z / math.sqrt(2.0)))
             + z * np.exp(-0.5 * z * z) / math.sqrt(2.0 * math.pi)),
    "swish": (lambda z: z * _sigmoid(z),
              lambda z: _sigmoid(z) * (1.0 + z * (1.0 - _sigmoid(z)))),
}


def _f64(a):
    return np.asarray(a.astype(jnp.float32) if a.dtype == jnp.bfloat16
                      else a, np.float64)


def _lower(op_type, ins, attrs, ctx=None):
    return get_op(op_type).lower(
        ctx or LowerCtx(), {k: [v] for k, v in ins.items()}, attrs)


def _vjp(op_type, ins, attrs, cotangents, wrt):
    """Outputs of the lowering and d sum(out * cotangent) / d ins[wrt]."""
    names = list(wrt)

    def f(*vals):
        out = _lower(op_type, dict(ins, **dict(zip(names, vals))), attrs)
        return {k: out[k][0] for k in cotangents}

    out, pull = jax.vjp(f, *[ins[n] for n in names])
    return out, dict(zip(names, pull(
        {k: jnp.asarray(v, out[k].dtype) for k, v in cotangents.items()})))


def _close(got, want, tol):
    np.testing.assert_allclose(_f64(jnp.asarray(got)), want, rtol=tol,
                               atol=tol)


# ---------------------------------------------------------------------------
# fc: mul + bias + activation
# ---------------------------------------------------------------------------
def _fc_case(seed, m, k, n, dtype="float32"):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(m, k), dtype),
            jnp.asarray(rng.randn(k, n) * 0.2, dtype),
            jnp.asarray(rng.randn(n), dtype))


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("act", sorted(ACTS))
def test_fc_matches_numpy_over_the_activation_table(act, bias):
    x, w, b = _fc_case(20, 24, 40, 48)
    ins = {"Input": x, "W": w, **({"Bias": b} if bias else {})}
    out = _lower("fc", ins, {"activation_type": act})["Out"][0]
    z = _f64(x) @ _f64(w) + (_f64(b) if bias else 0.0)
    assert out.shape == (24, 48) and out.dtype == jnp.float32
    _close(out, ACTS[act][0](z), 1e-5)


def test_fc_rejects_an_activation_outside_the_table():
    x, w, b = _fc_case(20, 8, 8, 8)
    with pytest.raises(ValueError, match="unsupported activation"):
        _lower("fc", {"Input": x, "W": w}, {"activation_type": "elu"})


def test_fc_odd_shapes_leading_dims_and_bf16():
    """A row count no block ever divided (7), rows spread over two leading
    dims (in_num_col_dims=2), and bfloat16 operands with a bfloat16
    result."""
    x, w, b = _fc_case(21, 7, 12, 20)
    out = _lower("fc", {"Input": x, "W": w, "Bias": b},
                 {"activation_type": "gelu"})["Out"][0]
    _close(out, ACTS["gelu"][0](_f64(x) @ _f64(w) + _f64(b)), 1e-5)

    x3 = jnp.asarray(np.random.RandomState(35).rand(4, 6, 16), jnp.float32)
    _, w3, b3 = _fc_case(35, 1, 16, 24)
    out = _lower("fc", {"Input": x3, "W": w3, "Bias": b3},
                 {"activation_type": "gelu", "in_num_col_dims": 2})["Out"][0]
    assert out.shape == (4, 6, 24)
    _close(out, ACTS["gelu"][0](_f64(x3) @ _f64(w3) + _f64(b3)), 1e-5)

    xb, wb, bb = _fc_case(21, 16, 24, 16, "bfloat16")
    out = _lower("fc", {"Input": xb, "W": wb, "Bias": bb},
                 {"activation_type": "swish"})["Out"][0]
    assert out.dtype == jnp.bfloat16
    _close(out, ACTS["swish"][0](_f64(xb) @ _f64(wb) + _f64(bb)), 3e-2)


@pytest.mark.parametrize("act", ["gelu", "swish", "tanh"])
def test_fc_grads_match_numpy(act):
    x, w, b = _fc_case(22, 16, 24, 32)
    dy = np.random.RandomState(7).uniform(0.5, 1.5, (16, 32))
    _, g = _vjp("fc", {"Input": x, "W": w, "Bias": b},
                {"activation_type": act}, {"Out": dy}, ("Input", "W", "Bias"))
    dz = dy * ACTS[act][1](_f64(x) @ _f64(w) + _f64(b))
    _close(g["Input"], dz @ _f64(w).T, 1e-4)
    _close(g["W"], _f64(x).T @ dz, 1e-4)
    _close(g["Bias"], dz.sum(0), 1e-4)


# ---------------------------------------------------------------------------
# fused_swiglu: silu(x Wg) * (x Wu)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,dtype,tol", [
    ((24, 20), "float32", 1e-5), ((2, 4, 8), "float32", 1e-5),
    ((24, 20), "bfloat16", 3e-2)], ids=["rows", "leading_dims", "bf16"])
def test_fused_swiglu_matches_numpy_with_grads(shape, dtype, tol):
    rng = np.random.RandomState(23)
    k, n = shape[-1], 16
    x = jnp.asarray(rng.randn(*shape), dtype)
    wg = jnp.asarray(rng.randn(k, n) * 0.3, dtype)
    wu = jnp.asarray(rng.randn(k, n) * 0.3, dtype)
    dy = rng.uniform(0.5, 1.5, shape[:-1] + (n,))
    out, grads = _vjp(
        "fused_swiglu", {"X": x, "GateW": wg, "UpW": wu},
        {"x_num_col_dims": len(shape) - 1}, {"Out": dy},
        ("X", "GateW", "UpW"))
    x2, d2 = _f64(x).reshape(-1, k), dy.reshape(-1, n)
    g, u = x2 @ _f64(wg), x2 @ _f64(wu)
    assert out["Out"].shape == shape[:-1] + (n,)
    assert out["Out"].dtype == jnp.dtype(dtype)
    _close(out["Out"].reshape(-1, n), ACTS["swish"][0](g) * u, tol)
    if dtype == "float32":  # bfloat16 cotangents round at every product
        dg, du = d2 * u * ACTS["swish"][1](g), d2 * ACTS["swish"][0](g)
        _close(grads["X"].reshape(-1, k),
               dg @ _f64(wg).T + du @ _f64(wu).T, 1e-4)
        _close(grads["GateW"], x2.T @ dg, 1e-4)
        _close(grads["UpW"], x2.T @ du, 1e-4)


# ---------------------------------------------------------------------------
# layer norm over the last axis, alone and behind a residual add
# ---------------------------------------------------------------------------
def _ln(s, gamma, beta, eps):
    mean, var = s.mean(-1, keepdims=True), s.var(-1, keepdims=True)
    xhat = (s - mean) / np.sqrt(var + eps)
    return xhat * gamma + beta, xhat, mean[..., 0], var[..., 0]


def _ln_grads(dy, xhat, var, gamma, eps):
    """(d s, d gamma, d beta) of y = xhat * gamma + beta."""
    dxhat = dy * gamma
    ds = (dxhat - dxhat.mean(-1, keepdims=True)
          - xhat * (dxhat * xhat).mean(-1, keepdims=True)) / np.sqrt(
              var[..., None] + eps)
    lead = tuple(range(dy.ndim - 1))
    return ds, (dy * xhat).sum(lead), dy.sum(lead)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 3e-2)])
def test_fused_residual_ln_matches_numpy_through_both_outputs(dtype, tol):
    """Sum (the residual stream the next op reads under the add's own
    name) and Y, statistics in float32 whatever the input; the gradient
    reaches x and y through BOTH outputs' cotangents."""
    rng = np.random.RandomState(24)
    x = jnp.asarray(rng.randn(2, 12, 32), dtype)
    y = jnp.asarray(rng.randn(2, 12, 32), dtype)
    gamma = jnp.asarray(rng.rand(32) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.randn(32), jnp.float32)
    d_sum, d_y = rng.randn(2, 12, 32), rng.uniform(0.5, 1.5, (2, 12, 32))
    ins = {"X": x, "Y": y, "Scale": gamma, "Bias": beta}
    out, grads = _vjp("fused_residual_ln", ins, {"epsilon": 1e-5},
                      {"Sum": d_sum, "Y": d_y}, ("X", "Y", "Scale", "Bias"))
    stats = _lower("fused_residual_ln", ins, {"epsilon": 1e-5})
    s = _f64(x) + _f64(y)
    want, xhat, mean, var = _ln(s, _f64(gamma), _f64(beta), 1e-5)
    assert out["Sum"].dtype == out["Y"].dtype == jnp.dtype(dtype)
    _close(out["Sum"], s, tol)
    _close(out["Y"], want, tol)
    for slot, ref in (("Mean", mean), ("Variance", var)):
        assert stats[slot][0].dtype == jnp.float32
        # the statistics are of the sum as it is handed on (rounded)
        _close(stats[slot][0], ref, 1e-5 if dtype == "float32" else 2e-2)
    if dtype == "float32":
        ds, dgamma, dbeta = _ln_grads(d_y, xhat, var, _f64(gamma), 1e-5)
        _close(grads["X"], ds + d_sum, 1e-4)
        _close(grads["Y"], ds + d_sum, 1e-4)
        _close(grads["Scale"], dgamma, 1e-4)
        _close(grads["Bias"], dbeta, 1e-4)


def test_layer_norm_of_a_bfloat16_input_keeps_float32_statistics():
    """The transformer case (the last axis, Scale and Bias): Y comes back
    in the input's dtype, Mean and Variance in float32 from the float32
    upcast of the bfloat16 values; gradients at float32 against the closed
    form (tests/test_ops_math.py::TestLayerNormOp holds the float32
    forward and a numeric gradient at [4, 6])."""
    rng = np.random.RandomState(4)
    gamma = jnp.asarray(rng.rand(64) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.randn(64), jnp.float32)
    xb = jnp.asarray(rng.randn(6, 64), jnp.bfloat16)
    out = _lower("layer_norm", {"X": xb, "Scale": gamma, "Bias": beta},
                 {"begin_norm_axis": 1, "epsilon": 1e-5})
    want, _, mean, var = _ln(_f64(xb), _f64(gamma), _f64(beta), 1e-5)
    assert out["Y"][0].dtype == jnp.bfloat16
    _close(out["Y"][0], want, 3e-2)
    for slot, ref in (("Mean", mean), ("Variance", var)):
        assert out[slot][0].dtype == jnp.float32
        _close(out[slot][0], ref, 1e-5)

    x = jnp.asarray(rng.randn(24, 64), jnp.float32)
    dy = rng.uniform(0.5, 1.5, (24, 64))
    got, grads = _vjp("layer_norm", {"X": x, "Scale": gamma, "Bias": beta},
                      {"begin_norm_axis": 1, "epsilon": 1e-5}, {"Y": dy},
                      ("X", "Scale", "Bias"))
    want, xhat, _, var = _ln(_f64(x), _f64(gamma), _f64(beta), 1e-5)
    _close(got["Y"], want, 1e-5)
    for g, ref in zip((grads["X"], grads["Scale"], grads["Bias"]),
                      _ln_grads(dy, xhat, var, _f64(gamma), 1e-5)):
        _close(g, ref, 1e-4)


# ---------------------------------------------------------------------------
# softmax_with_cross_entropy
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rows,classes", [(128, 1000), (37, 1000)],
                         ids=["resnet_128x1000", "ragged_37x1000"])
def test_softmax_with_cross_entropy_matches_numpy_with_its_gradient(
        rows, classes):
    """ResNet-50's loss shape and a row count no block divided
    (tests/test_ops_math.py::TestSoftmaxWithCrossEntropy holds a small
    [5, 8] with a numeric gradient)."""
    rng = np.random.RandomState(34)
    logits = jnp.asarray(rng.randn(rows, classes) * 3, jnp.float32)
    label = rng.randint(0, classes, (rows, 1))
    dy = rng.randn(rows, 1)
    out, grads = _vjp(
        "softmax_with_cross_entropy",
        {"Logits": logits, "Label": jnp.asarray(label, jnp.int32)}, {},
        {"Loss": dy}, ("Logits",))
    z = _f64(logits)
    e = np.exp(z - z.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    onehot = np.eye(classes)[label[:, 0]]
    _close(out["Loss"], -np.log((p * onehot).sum(-1, keepdims=True)), 1e-5)
    _close(grads["Logits"], (p - onehot) * dy, 1e-5)
    soft = _lower("softmax_with_cross_entropy",
                  {"Logits": logits, "Label": jnp.asarray(label, jnp.int32)},
                  {})["Softmax"][0]
    _close(soft, p, 1e-5)


# ---------------------------------------------------------------------------
# fused_attention with QStart: chunked decode and the ragged serving step
# ---------------------------------------------------------------------------
def _attention_loop(q, k, v, starts, window, d_out):
    """Row by row and query by query: query i of batch row b sits at
    position starts[b] + i and sees keys j <= that position (and, under a
    window, the last `window` of them).  Returns (out, dq, dk, dv) for the
    cotangent d_out."""
    b_, h_, tq, d = q.shape
    scale = 1.0 / math.sqrt(d)
    out = np.zeros_like(q)
    dq, dk, dv = np.zeros_like(q), np.zeros_like(k), np.zeros_like(v)
    for b in range(b_):
        for h in range(h_):
            for i in range(tq):
                pos = starts[b] + i
                lo = max(0, pos - window + 1) if window else 0
                ks, vs = k[b, h, lo:pos + 1], v[b, h, lo:pos + 1]
                s = ks @ q[b, h, i] * scale
                p = np.exp(s - s.max())
                p /= p.sum()
                out[b, h, i] = p @ vs
                dp = vs @ d_out[b, h, i]
                ds = p * (dp - p @ dp)
                dq[b, h, i] = ds @ ks * scale
                dk[b, h, lo:pos + 1] += np.outer(ds, q[b, h, i]) * scale
                dv[b, h, lo:pos + 1] += np.outer(p, d_out[b, h, i])
    return out, dq, dk, dv


def _qkv(seed, b, h, tq, tk, d):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, h, tq, d), jnp.float32),
            jnp.asarray(rng.randn(b, h, tk, d), jnp.float32),
            jnp.asarray(rng.randn(b, h, tk, d), jnp.float32),
            rng.uniform(0.5, 1.5, (b, h, tq, d)))


def _check_attention(q, k, v, d_out, qstart, starts, window):
    out, grads = _vjp(
        "fused_attention",
        {"Q": q, "K": k, "V": v, "QStart": jnp.asarray(qstart, jnp.int32)},
        {"causal": True, "window": window}, {"Out": d_out}, ("Q", "K", "V"))
    want = _attention_loop(_f64(q), _f64(k), _f64(v), starts, window, d_out)
    _close(out["Out"], want[0], 2e-5)
    for g, ref in zip((grads["Q"], grads["K"], grads["V"]), want[1:]):
        _close(g, ref, 2e-4)


def test_fused_attention_per_row_qstart_matches_a_per_row_loop():
    """Rows of different starts in one call, Tq (4) against a cache of
    Tk (16), a start of 0 and one whose last query reaches the last key."""
    q, k, v, d_out = _qkv(32, 3, 2, 4, 16, 8)
    _check_attention(q, k, v, d_out, [0, 5, 12], [0, 5, 12], 0)


@pytest.mark.parametrize("window", [0, 5], ids=["full", "window_5"])
def test_fused_attention_scalar_qstart_matches_the_loop(window):
    q, k, v, d_out = _qkv(31, 2, 2, 4, 16, 8)
    _check_attention(q, k, v, d_out, [7], [7, 7], window)


def test_fused_attention_qstart_refuses_malformed_input():
    q, k, v, _ = _qkv(30, 3, 2, 4, 16, 8)
    ins = {"Q": q, "K": k, "V": v}
    two = jnp.asarray([0, 5], jnp.int32)
    with pytest.raises(ValueError, match=r"vector QStart must be \[batch\]=3"):
        _lower("fused_attention", dict(ins, QStart=two), {"causal": True})
    with pytest.raises(ValueError, match="window is not supported with per-row"):
        _lower("fused_attention",
               dict(ins, QStart=jnp.asarray([0, 5, 9], jnp.int32)),
               {"causal": True, "window": 4})
    with pytest.raises(ValueError, match="QStart requires causal"):
        _lower("fused_attention", dict(ins, QStart=two[:1]), {})


def test_fused_attention_per_row_qstart_on_a_mesh_equals_unsharded():
    """Under a live dp1 x mp2 mesh the per-row form shards the heads of a
    4-D einsum (nn_ops._qvec_attention_mesh): the same numbers as the
    unsharded lowering, out and the gradients, the result laid out over
    mp by heads."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.partition_rules import (
        spmd_lowering, train_partition_rules_for)

    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    q, k, v, d_out = _qkv(33, 3, 2, 4, 16, 8)
    qs = jnp.asarray([0, 5, 12], jnp.int32)
    d_out = jnp.asarray(d_out, jnp.float32)

    def step(q, k, v):
        out, pull = jax.vjp(lambda q, k, v: _lower(
            "fused_attention", {"Q": q, "K": k, "V": v, "QStart": qs},
            {"causal": True})["Out"][0], q, k, v)
        return (out,) + pull(d_out)

    want = jax.jit(step)(q, k, v)
    mesh = make_mesh({"dp": 1, "mp": 2}, jax.devices()[:2])
    heads = NamedSharding(mesh, P(None, "mp", None, None))
    with spmd_lowering(mesh, train_partition_rules_for("gpt2")):
        got = jax.jit(step)(*(jax.device_put(a, heads) for a in (q, k, v)))
    assert got[0].sharding.is_equivalent_to(heads, 4)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# the flags that chose the other lowerings are gone
# ---------------------------------------------------------------------------
def test_the_lowering_flags_are_gone_and_setting_one_raises():
    gone = ("use_pallas", "flash_block_q", "flash_block_k",
            "kernel_tune_cache", "kernel_autotune")
    assert not set(gone) & set(flags.flag_items())
    assert len(flags.flag_items()) == 31
    with pytest.raises(KeyError, match="unknown flag use_pallas"):
        flags.set_flags({"use_pallas": 1})
