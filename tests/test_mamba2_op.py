"""mamba2_scan: the Mamba-2 selective scan's chunkwise lowering
(ops/mamba2_ops.py, its three Pallas kernels interpreted here) against the
token-by-token recurrence it stands for, written here in a lax.scan over T:
the result and every input's gradient (x, dt, A, B, C, D), at lengths that
are whole chunks (128, 256) and that pad (1, 127, 129, 200, 300), one chunk
and many, eight heads a group, two, and one (H / G of 8, 2 and 1), a head
whose dt A is -10 a token beside one at -0.001 (finite, and the neighbour
unchanged), bfloat16 operands with the float32 parts float32 (read off the
traced step); through a Program with its grad op, under the AMP pass, its
infer rule and its refusals by name, its line in program_flops and what it
leaves in attribution()."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, framework, layers, unique_name
from paddle_tpu.analysis.infer import InferError, VarInfo, get_infer_rule
from paddle_tpu.ops import kernel_tuning, mamba2_ops

B, H, P, N = 2, 8, 8, 16
INPUTS = ("X", "Dt", "A", "B", "C", "D")


def recurrence(x, dt, a, b, c, d):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D x_t:
    one token a step; x [B, H, T, P], dt [B, H, T], a, d [H], b, c
    [B, G, T, N], head j reads group j // (H / G)."""
    rep = x.shape[1] // b.shape[1]
    b, c = jnp.repeat(b, rep, 1), jnp.repeat(c, rep, 1)

    def step(s, v):
        xt, dtt, bt, ct = v
        s = (jnp.exp(dtt * a)[..., None, None] * s
             + (dtt[..., None] * xt)[..., None] * bt[..., None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, ct) + d[:, None] * xt

    xs = [jnp.moveaxis(v, 2, 0) for v in (x, dt, b, c)]
    _, y = jax.lax.scan(
        step, jnp.zeros(x.shape[:2] + (x.shape[-1], b.shape[-1])), xs)
    return jnp.moveaxis(y, 0, 2)


def _data(t, kind="mixed", g=1, h=H):
    """x, B, C normal, dt log-uniform in (0.001, 0.1) and A uniform(-16,
    -1) a head as the model initialises them, D normal; `mix` weights the
    result so that the loss is no constant."""
    rng = np.random.RandomState(57 + t)
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), (B, h, t)))
    a = -rng.uniform(1.0, 16.0, (h,))
    if kind == "fast_beside_slow":  # dt A = -10 a token on the even heads,
        dt = np.ones_like(dt)       # -0.001 on the odd ones
        a = np.where(np.arange(h) % 2 == 0, -10.0, -0.001)
    elif kind == "slow":  # a chunk hands most of its state on
        dt = dt / 20.0
    return {"X": rng.randn(B, h, t, P).astype("float32"),
            "Dt": dt.astype("float32"), "A": a.astype("float32"),
            "B": rng.randn(B, g, t, N).astype("float32"),
            "C": rng.randn(B, g, t, N).astype("float32"),
            "D": rng.randn(h).astype("float32"),
            "mix": rng.uniform(0.5, 1.5, (B, h, t, P)).astype("float32")}


@functools.lru_cache(maxsize=None)
def _both(t, kind="mixed", g=1, h=H):
    """((result, gradients by input) of the op's lowering, the same of the
    recurrence)."""
    w = _data(t, kind, g, h)
    args = [jnp.asarray(w[n]) for n in INPUTS]
    out = []
    with jax.default_matmul_precision("highest"):
        for f in (mamba2_ops.mamba2_scan, recurrence):
            y, pull = jax.jit(lambda *a: jax.vjp(f, *a))(*args)
            out.append((np.asarray(y), dict(zip(INPUTS, map(
                np.asarray, jax.jit(pull)(jnp.asarray(w["mix"])))))))
    return out


# every length with eight heads a group; each special decay where a chunk
# is whole, where it pads and over several chunks; groups of two heads, and
# of one (H / G = 1: every head its own B and C)
CASES = ([(t, "mixed", 1, H) for t in (1, 127, 128, 129, 200, 256, 300)]
         + [(128, "slow", 1, H), (300, "slow", 1, H),
            (200, "fast_beside_slow", 1, H),
            (128, "fast_beside_slow", 1, H),
            (200, "mixed", 4, H), (300, "slow", 4, H),
            (200, "mixed", H, H), (129, "slow", 2, 2)])


@pytest.mark.parametrize("t, kind, g, h", CASES)
def test_the_chunkwise_result_is_the_recurrences(t, kind, g, h):
    (got, _), (want, _) = _both(t, kind, g, h)
    assert got.shape == want.shape == (B, h, t, P)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("wrt", INPUTS)
@pytest.mark.parametrize("t, kind, g, h", CASES)
def test_every_gradient_is_jax_grad_of_the_recurrence(t, kind, g, h, wrt):
    """The op's own backward (the walk that keeps the entering states, then
    the reverse walk with dS in the scratch: the chunk's inside transposed
    by hand, dB and dC summed over a group's heads, the running sum
    transposed in the kernel) against autodiff of the recurrence: 1e-4 of
    the gradient's largest element."""
    (_, got), (_, want) = _both(t, kind, g, h)
    u, w = got[wrt], want[wrt]
    assert u.shape == w.shape and np.isfinite(u).all()
    assert np.abs(u - w).max() <= 1e-4 * max(np.abs(w).max(), 1e-3), wrt


def test_a_head_that_forgets_in_a_token_is_finite_beside_one_that_does_not():
    """dt A = -10 a token on the even heads (exp(+1280) over a chunk if it
    were ever taken), -0.001 on the odd ones: everything finite; a head
    that forgets reads what its own token wrote, dt (C.B) x + D x, to a
    part in 2e4; and the slow neighbours are what they are when the fast
    heads are given THEIR slow decay too (a flushed or overflowed
    neighbour would show there)."""
    (got, grads), (want, _) = _both(200, "fast_beside_slow")
    assert np.isfinite(got).all()
    assert all(np.isfinite(v).all() for v in grads.values())
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    w = _data(200, "fast_beside_slow")
    cb = (w["C"] * w["B"]).sum(-1)[:, :, :, None]  # one group: [B, 1, T, 1]
    own = (cb + w["D"][None, :, None, None]) * w["X"]
    fast = np.arange(H) % 2 == 0
    assert np.abs(got[:, fast] - own[:, fast]).max() < 1e-4 * np.abs(
        own[:, fast]).max() + 1e-3
    assert np.abs(got[:, ~fast] - own[:, ~fast]).max() > 1.0  # they remember
    slow = dict(w, A=np.full_like(w["A"], -0.001))
    with jax.default_matmul_precision("highest"):
        alone = np.asarray(mamba2_ops.mamba2_scan(
            *[jnp.asarray(slow[n]) for n in INPUTS]))
    np.testing.assert_allclose(got[:, ~fast], alone[:, ~fast], rtol=1e-5,
                               atol=1e-5)


def test_head_j_reads_group_j_over_the_heads_a_group():
    """Heads 0, 1 read group 0, heads 2, 3 group 1 (the published
    repeat_interleave): the op fed four groups under eight heads is the op
    fed those groups repeated to eight, and is NOT the op fed them tiled
    (head j reading group j mod 4)."""
    w = _data(200, g=4)
    args = {n: jnp.asarray(w[n]) for n in INPUTS}
    with jax.default_matmul_precision("highest"):
        got = mamba2_ops.mamba2_scan(*args.values())
        same = mamba2_ops.mamba2_scan(*dict(
            args, B=jnp.repeat(args["B"], 2, 1),
            C=jnp.repeat(args["C"], 2, 1)).values())
        tiled = mamba2_ops.mamba2_scan(*dict(
            args, B=jnp.tile(args["B"], (1, 2, 1, 1)),
            C=jnp.tile(args["C"], (1, 2, 1, 1))).values())
    np.testing.assert_allclose(got, same, rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(got) - np.asarray(tiled)).max() > 0.1


def test_output_at_t_does_not_see_inputs_after_t():
    w = _data(200)
    args = [jnp.asarray(w[n]) for n in INPUTS]
    later = [a.at[:, :, 150:].set(3.0) if a.ndim > 1 else a for a in args]
    got, moved = (np.asarray(mamba2_ops.mamba2_scan(*a))
                  for a in (args, later))
    np.testing.assert_array_equal(got[:, :, :150], moved[:, :, :150])
    assert np.abs(got[:, :, 150:] - moved[:, :, 150:]).max() > 0.1


# --- bfloat16 operands, float32 parts ----------------------------------------
def _half(t, kind="mixed", g=1):
    w = _data(t, kind, g)
    args = [jnp.asarray(w[n]) for n in INPUTS]
    narrow = (0, 3, 4)  # x, B, C
    return [a.astype(jnp.bfloat16) if i in narrow else a
            for i, a in enumerate(args)], jnp.asarray(w["mix"])


def test_bf16_operands_float32_decay_and_state():
    """bf16 x, B, C with float32 dt, A, D: a bf16 result within bf16
    rounding of the float32 recurrence on the same (rounded) inputs, bf16
    gradients for the operands and float32 ones for the float32 parts."""
    half, _ = _half(300, "slow")
    got = mamba2_ops.mamba2_scan(*half)
    assert got.dtype == jnp.bfloat16
    want = recurrence(*[a.astype(jnp.float32) for a in half])
    assert np.abs(np.asarray(got, "float32") - np.asarray(want)).max() < (
        0.02 * np.abs(np.asarray(want)).max())
    grads = jax.grad(lambda *a: mamba2_ops.mamba2_scan(*a).astype(
        jnp.float32).sum(), argnums=range(6))(*half)
    assert [str(v.dtype) for v in grads] == [
        "bfloat16", "float32", "float32", "bfloat16", "bfloat16", "float32"]


@functools.lru_cache(maxsize=None)
def _half_grads():
    half, mix = _half(300, "slow")
    return [jax.grad(lambda *a: (f(*a).astype(jnp.float32) * mix).sum(),
                     argnums=range(6))(*x)
            for f, x in ((mamba2_ops.mamba2_scan, half),
                         (recurrence, [a.astype(jnp.float32) for a in half]))]


@pytest.mark.parametrize("wrt", INPUTS)
def test_bf16_operands_every_gradient_is_the_recurrences(wrt):
    """Slow decays over three chunks, so that the entering states and the
    gradient through a chunk's whole decay count: every gradient within 2%
    of the largest element of the float32 recurrence's on the same
    (rounded) inputs."""
    got, want = (np.asarray(v[INPUTS.index(wrt)], "float32")
                 for v in _half_grads())
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 0.02 * np.abs(want).max()


def _pallas_calls(jaxpr):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub))
    return found


def test_the_float32_parts_are_float32_read_off_the_traced_step():
    """Under bf16 operands: three kernels and no scan; each holds its
    scratch [R, P, N] float32 (the carried state, and its gradient in the
    reverse walk); dt and dt A enter every kernel as float32 rows, A and D
    as float32 numbers a head; the backward's first walk stacks the
    entering states float32; dB and dC leave in the operands' dtype, summed
    over the group's heads before the cast."""
    half, _ = _half(300, "slow", g=2)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda *a: mamba2_ops.mamba2_scan(*a).astype(jnp.float32).sum(),
        argnums=range(6)))(*half)
    calls = _pallas_calls(jaxpr.jaxpr)
    assert [len(c.outvars) for c in calls] == [1, 1, 6]
    rows = ((B, H, 3, 1, 128), jnp.float32)
    for call in calls:
        ins = [(v.aval.shape, v.aval.dtype) for v in call.invars]
        assert ins[0] == ((B, H, 384, P), jnp.bfloat16)
        assert ins[1] == ins[2] == rows  # dt and dt A, padded to 3 chunks
        scratch = call.params["grid_mapping"].scratch_avals
        assert [(s.shape, s.dtype) for s in scratch] == [
            ((H // 2, P, N), jnp.float32)]
    states = calls[1].outvars[0].aval
    assert (states.shape, states.dtype) == ((3, B, H, P, N), jnp.float32)
    assert ((H, 1, 1), jnp.float32) in [
        (v.aval.shape, v.aval.dtype) for v in calls[0].invars]
    outs = [(v.aval.shape, str(v.aval.dtype)) for v in calls[2].outvars]
    assert outs == [((B, H, 384, P), "bfloat16")] + [
        (rows[0], "float32")] * 3 + [((B, 2, 384, N), "bfloat16")] * 2

    def scans(j):
        return sum((e.primitive.name == "scan")
                   + sum(scans(s) for s in jax.core.jaxprs_in_params(
                       e.params)) for e in j.eqns)

    assert scans(jaxpr.jaxpr) == 0


# --- through a Program --------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _program(t, g=2):
    w = _data(t, g=g)
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        ins = []
        for n in INPUTS:
            x = layers.data(n, shape=list(w[n].shape),
                            append_batch_size=False)
            x.stop_gradient = False
            ins.append(x)
        mix = layers.data("mix", shape=list(w["mix"].shape),
                          append_batch_size=False)
        y = layers.mamba2_scan(*ins)
        loss = layers.reduce_sum(layers.elementwise_mul(y, mix))
        fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        out = exe.run(main, feed=w, fetch_list=[y] + [
            main._grad_names[n] for n in INPUTS])
    return main, loss, y, out


def test_the_layer_builds_one_op_with_its_grad_op_and_it_verifies():
    main, loss, y, out = _program(129)
    types = [op.type for op in main.global_block().ops]
    assert types.count("mamba2_scan") == 1
    assert types.count("mamba2_scan_grad") == 1
    assert tuple(y.shape) == (B, H, 129, P) and str(y.dtype) == "float32"
    assert not [d for d in analysis.verify_program(main, fetches=[loss])
                if d.is_error]
    with jax.default_matmul_precision("highest"):
        (got, grads), _ = _both(129, "mixed", 2)
    np.testing.assert_allclose(out[0], got, rtol=1e-4, atol=1e-4)
    for n, v in zip(INPUTS, out[1:]):
        assert np.abs(v - grads[n]).max() <= 1e-3 * np.abs(grads[n]).max(), n


def test_attribution_counts_the_kernels_engagements():
    """`pallas_hits["ssd"]`: the forward op's scan, and the grad op's three
    (its forward, traced and then dead, the walk that keeps the states and
    the reverse walk)."""
    kernel_tuning.reset_attribution()
    _program.cache_clear()
    _program(129)
    assert kernel_tuning.attribution()["pallas_hits"]["ssd"] == 4


def test_amp_pass_narrows_x_b_c_and_keeps_the_step_the_rate_and_the_skip():
    from paddle_tpu.transpiler.pass_registry import apply_pass

    shapes = {"X": [B, H, 70, P], "Dt": [B, H, 70], "A": [H],
              "B": [B, 2, 70, N], "C": [B, 2, 70, N], "D": [H]}
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        ins = [layers.data(n, shape=shapes[n], append_batch_size=False)
               for n in INPUTS]
        layers.mamba2_scan(*ins)
        apply_pass(main, "bf16_amp_pass")
    block = main.global_block()
    (op,) = [o for o in block.ops if o.type == "mamba2_scan"]
    dtypes = {slot: str(block.var(names[0]).dtype)
              for slot, names in list(op.inputs.items())
              + list(op.outputs.items())}
    assert dtypes == {"X": "bfloat16", "B": "bfloat16", "C": "bfloat16",
                      "Dt": "float32", "A": "float32", "D": "float32",
                      "Out": "bfloat16"}


def _infer(x, dt, b, c=None, a=None, d=None):
    class Op:
        attrs = {}

    heads = (x[1],) if len(x) > 1 else (1,)
    return get_infer_rule("mamba2_scan").fn(Op, {
        "X": [VarInfo(x, "bfloat16")], "Dt": [VarInfo(dt, "float32")],
        "A": [VarInfo(a or heads, "float32")],
        "B": [VarInfo(b, "bfloat16")], "C": [VarInfo(c or b, "bfloat16")],
        "D": [VarInfo(d or heads, "float32")]})


def test_infer_rule_gives_xs_shape_and_dtype():
    out = _infer((-1, 8, 70, 16), (-1, 8, 70), (-1, 2, 70, 32))["Out"][0]
    assert out.shape == (-1, 8, 70, 16) and out.dtype == "bfloat16"


@pytest.mark.parametrize("kwargs, says", [
    (dict(x=(2, 8, 70), dt=(2, 8, 70), b=(2, 2, 70, 32)), r"wants X \["),
    (dict(x=(2, 8, 70, 16), dt=(2, 8, 71), b=(2, 2, 70, 32)), "Dt"),
    (dict(x=(2, 8, 70, 16), dt=(2, 4, 70), b=(2, 2, 70, 32)), "Dt"),
    (dict(x=(2, 8, 70, 16), dt=(2, 8, 70), b=(2, 2, 70, 32),
          c=(2, 2, 70, 16)), "B and C"),
    (dict(x=(2, 8, 70, 16), dt=(2, 8, 70), b=(2, 2, 71, 32)), "B and C"),
    (dict(x=(2, 8, 70, 16), dt=(2, 8, 70), b=(2, 3, 70, 32)),
     "3 groups do not divide X's 8 heads"),
    (dict(x=(2, 8, 70, 16), dt=(2, 8, 70), b=(2, 2, 70, 32), a=(4,)),
     r"A\(4,\) is not \[8\]"),
    (dict(x=(2, 8, 70, 16), dt=(2, 8, 70), b=(2, 2, 70, 32), d=(8, 1)),
     r"D\(8, 1\) is not \[8\]")])
def test_infer_rule_refuses_inconsistent_edges_by_name(kwargs, says):
    with pytest.raises(InferError, match="mamba2_scan.*" + says):
        _infer(**kwargs)


def test_program_flops_counts_the_chunkwise_form():
    """A token 2 Q N a group and 2 Q P + 4 N P a head, the grad op
    twice."""
    from paddle_tpu.utils.flops import program_flops

    main = _program(129)[0]
    one = B * 129 * (2 * 2.0 * 128 * N + H * (2.0 * 128 * P + 4.0 * N * P))
    assert program_flops(main) == 3.0 * one
