"""gated_delta_attention: Gated DeltaNet's chunkwise lowering
(ops/kda_ops.gdn_chunked, its chunk inside the two Pallas kernels
kda_kernels.gdn_intra / gdn_intra_bwd, interpreted here) against the
token-by-token recurrence it stands for, written here in a lax.scan over
T with ONE decay a head: the result and every input's gradient, at beta =
0 (pure decay), g = 0 (the plain delta rule), log-decays down to -5 a
token a head (exp(+320) over a chunk if it were ever taken) and so slow
that a chunk hands half its state on, key heads shared by several value
heads and not, lengths that pad (1, 63, 65, 200, 600) and that do not
(64), one chunk a grid step and several, the narrow heads of most cases
and the cell's (dk = dv = 128); against `kda_attention` fed the same decay
on every channel (the two members of the family agree); through a Program
with its grad op, under the AMP pass, its infer rule, its line in
program_flops and what it leaves in attribution(); and the carry's kernels
(kda_kernels.carry / carry_bwd, KDA's code) fed the parts of this member's
own inside, against the docstring's equations as a plain lax.scan
(tests/delta_rule_carry.py; at random parts under either decay:
tests/test_kda_op.py)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, framework, layers, unique_name
from paddle_tpu.analysis.infer import InferError, VarInfo, get_infer_rule
from paddle_tpu.ops import kda_kernels, kda_ops, kernel_tuning

import delta_rule_carry as plain

B, HK, HV, DK, DV = 2, 2, 4, 16, 8
SCALE = DK ** -0.5
INPUTS = ("Q", "K", "V", "G", "Beta")


def recurrence(q, k, v, g, beta, scale=SCALE):
    """S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T;
    o_t = S_t^T (scale q_t): one token a step; q, k [B, Hk, T, dk], value
    head j reads key head j // (Hv / Hk); g, beta [B, Hv, T]."""
    rep = v.shape[1] // q.shape[1]
    q, k = jnp.repeat(q, rep, 1), jnp.repeat(k, rep, 1)

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[..., None, None] * s
        u = bt[..., None] * (vt - jnp.einsum("bhc,bhcv->bhv", kt, s))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.einsum("bhc,bhcv->bhv", qt * scale, s)

    xs = [jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta)]
    _, o = jax.lax.scan(
        step, jnp.zeros(v.shape[:2] + (q.shape[-1], v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 2)


def _data(t, kind="mixed", dk=DK, dv=DV, hk=HK, hv=HV):
    """q and k on the unit sphere (as the model's L2 norm leaves them), v
    normal, beta in (0, 1), g by `kind`; `mix` weights the result so that
    the loss is no constant."""
    rng = np.random.RandomState(11 + t)
    q, k = (rng.randn(B, hk, t, dk).astype("float32") for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -rng.uniform(0.001, 1.6, (B, hv, t)).astype("float32")
    beta = rng.uniform(0.05, 0.95, (B, hv, t)).astype("float32")
    if kind == "pure_decay":
        beta = np.zeros_like(beta)
    elif kind == "no_decay":
        g = np.zeros_like(g)
    elif kind == "fast":  # half the tokens forget all before them
        g = np.where(rng.rand(*g.shape) < 0.5, -5.0, g).astype("float32")
    elif kind == "all_fast":
        g = np.full_like(g, -5.0)
    elif kind == "slow":  # exp(G_C) ~ 0.5: the states reach far, and the
        g = g / 80.0      # chunk's whole decay has a gradient that counts
    return {"Q": q, "K": k, "V": rng.randn(B, hv, t, dv).astype("float32"),
            "G": g, "Beta": beta,
            "mix": rng.uniform(0.5, 1.5, (B, hv, t, dv)).astype("float32")}


@functools.lru_cache(maxsize=None)
def _both(t, kind, width=DK, hk=HK):
    """((result, gradients by input) of the op's lowering, the same of the
    recurrence); `width`: dk, and dv where it is not the narrow DK; `hk`
    key heads under the HV value heads."""
    w = _data(t, kind, width, DV if width == DK else width, hk)
    args = [jnp.asarray(w[n]) for n in INPUTS]
    scale = width ** -0.5
    out = []
    with jax.default_matmul_precision("highest"):
        for f in (lambda *a: kda_ops.gdn_chunked(*a, scale),
                  lambda *a: recurrence(*a, scale)):
            o, pull = jax.jit(lambda *a: jax.vjp(f, *a))(*args)
            out.append((np.asarray(o), dict(zip(INPUTS, map(
                np.asarray, jax.jit(pull)(jnp.asarray(w["mix"])))))))
    return out


# every length with mixed decays and grouped heads (2 key heads under 4
# value heads); each special decay where a chunk is whole, where it pads
# and over several chunks (T = 200 is four chunks in one grid step, T = 600
# ten, padded to two steps of eight); heads that are not grouped, and one
# key head under all four; at the cell's head shape, dk = dv = 128, a
# length that pads and one that forgets in a token
CASES = ([(t, "mixed", DK, HK) for t in (1, 63, 64, 65, 200, 600)]
         + [(65, "pure_decay", DK, HK), (200, "pure_decay", DK, HK),
            (64, "no_decay", DK, HK), (200, "no_decay", DK, HK),
            (65, "fast", DK, HK), (200, "fast", DK, HK),
            (63, "all_fast", DK, HK), (200, "all_fast", DK, HK),
            (200, "slow", DK, HK), (200, "mixed", DK, HV),
            (200, "mixed", DK, 1), (65, "fast", DK, 1),
            (130, "mixed", 128, HK), (130, "fast", 128, HK)])


@pytest.mark.parametrize("t, kind, width, hk", CASES)
def test_the_chunkwise_result_is_the_recurrences(t, kind, width, hk):
    (got, _), (want, _) = _both(t, kind, width, hk)
    assert got.shape == want.shape == (B, HV, t,
                                       DV if width == DK else width)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("wrt", INPUTS)
@pytest.mark.parametrize("t, kind, width, hk", CASES)
def test_every_gradient_is_jax_grad_of_the_recurrence(t, kind, width, hk,
                                                     wrt):
    """The op's own backward (kernel 1 again for the carry's operands, the
    carry forward for the entering states and backwards, then the
    transposed inside by hand in kernel 2 and the sum over a key head's
    readers) against autodiff of the recurrence: 1e-4 of the gradient's
    largest element."""
    (_, got), (_, want) = _both(t, kind, width, hk)
    g, w = got[wrt], want[wrt]
    assert g.shape == w.shape and np.isfinite(g).all()
    assert np.abs(g - w).max() <= 1e-4 * max(np.abs(w).max(), 1e-3), wrt


def test_a_pure_decay_writes_nothing_and_a_first_token_reads_itself():
    """beta = 0: the state stays zero and so does the result, whatever
    the decay; at t = 0 the state is beta k v^T, so o_0 = beta (q.k) v,
    q and k those of the key head the value head reads."""
    (got, _), _ = _both(65, "pure_decay")
    assert np.abs(got).max() == 0.0
    w = _data(65, "mixed")
    (got, _), _ = _both(65, "mixed")
    qk = np.repeat((w["Q"][..., 0, :] * w["K"][..., 0, :]).sum(
        -1, keepdims=True), HV // HK, axis=1)
    first = w["Beta"][..., 0, None] * SCALE * qk * w["V"][..., 0, :]
    np.testing.assert_allclose(got[..., 0, :], first, rtol=1e-5, atol=1e-6)


def test_a_head_that_forgets_in_a_token_gives_neither_inf_nor_a_flush():
    """g = -5 on every head of every token: exp(+cumsum) would be exp(320)
    inside a chunk.  The result is finite and the recurrence's to 1e-4,
    and it is not the zero a flushed state would give: o_t is within 2% of
    what token t alone wrote (the rest decayed by exp(-5) a step)."""
    (got, grads), (want, _) = _both(200, "all_fast")
    assert np.isfinite(got).all()
    assert all(np.isfinite(g).all() for g in grads.values())
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    w = _data(200, "all_fast")
    qk = np.repeat((w["Q"] * w["K"]).sum(-1, keepdims=True), HV // HK, 1)
    own = w["Beta"][..., None] * SCALE * qk * w["V"]
    assert np.abs(got).max() > 0.1
    assert np.abs(got - own).max() < 0.02 * np.abs(own).max()


def test_value_head_j_reads_key_head_j_over_the_group():
    """The published repeat_interleave: value heads 0 and 1 read key head
    0, heads 2 and 3 key head 1.  The op fed two key heads is the op fed
    those heads repeated to four, and is NOT the op fed them tiled (value
    head j reading key head j mod 2)."""
    w = _data(130, "mixed")
    args = [jnp.asarray(w[n]) for n in INPUTS]
    got = kda_ops.gdn_chunked(*args, SCALE)
    each = kda_ops.gdn_chunked(
        jnp.repeat(args[0], 2, 1), jnp.repeat(args[1], 2, 1), *args[2:],
        SCALE)
    np.testing.assert_allclose(got, each, rtol=1e-6, atol=1e-7)
    tiled = kda_ops.gdn_chunked(
        jnp.tile(args[0], (1, 2, 1, 1)), jnp.tile(args[1], (1, 2, 1, 1)),
        *args[2:], SCALE)
    assert np.abs(np.asarray(got) - np.asarray(tiled)).max() > 0.1


@pytest.mark.parametrize("t, kind", [(65, "mixed"), (200, "fast"),
                                     (200, "slow"), (64, "no_decay")])
def test_the_two_members_of_the_family_agree(t, kind):
    """`kda_attention` fed the head's decay on every channel (and every key
    head repeated to its readers) is `gated_delta_attention`: result and
    the gradients, the decay's summed over the channels and q's and k's
    over a key head's readers."""
    w = _data(t, kind)
    args = [jnp.asarray(w[n]) for n in INPUTS]
    mix = jnp.asarray(w["mix"])

    def per_channel(q, k, v, g, beta):
        q, k = (jnp.repeat(x, HV // HK, 1) for x in (q, k))
        g = jnp.broadcast_to(g[..., None], g.shape + (DK,))
        return kda_ops.kda_chunked(q, k, v, g, beta, SCALE)

    with jax.default_matmul_precision("highest"):
        got, pull = jax.vjp(lambda *a: kda_ops.gdn_chunked(*a, SCALE), *args)
        want, pull_kda = jax.vjp(per_channel, *args)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        for n, a, b in zip(INPUTS, pull(mix), pull_kda(mix)):
            assert np.abs(a - b).max() <= 1e-4 * max(
                float(np.abs(b).max()), 1e-3), n


def test_output_at_t_does_not_see_inputs_after_t():
    w = _data(200)
    cut = 70
    later = {n: w[n].copy() for n in INPUTS}
    for n in ("Q", "V"):
        later[n][:, :, cut + 1:] += 3.0
    later["K"][:, :, cut + 1:] = _data(200, "fast")["Q"][:, :, cut + 1:]
    later["G"][:, :, cut + 1:] -= 1.0
    a, b = (np.asarray(kda_ops.gdn_chunked(
        *[jnp.asarray(x[n]) for n in INPUTS], SCALE)) for x in (w, later))
    np.testing.assert_array_equal(a[:, :, :cut + 1], b[:, :, :cut + 1])
    assert np.abs(a[:, :, cut + 1] - b[:, :, cut + 1]).max() > 0.1


@pytest.mark.parametrize("block", [1, 2])
def test_several_chunks_a_grid_step_are_one_a_step(monkeypatch, block):
    """T = 200 is four chunks: one grid step a head at BLOCK 8, two at
    BLOCK 2, four at BLOCK 1: the same result and gradients (to rounding:
    the products are the same, batched otherwise)."""
    (want, want_grads), _ = _both(200, "mixed")
    monkeypatch.setattr(kda_ops, "BLOCK", block)
    assert kda_ops._block(200) == block
    w = _data(200, "mixed")
    args = [jnp.asarray(w[n]) for n in INPUTS]
    with jax.default_matmul_precision("highest"):
        got = kda_ops.gdn_chunked(*args, SCALE)
        grads = jax.grad(lambda *a: (kda_ops.gdn_chunked(*a, SCALE)
                                     * w["mix"]).sum(),
                         argnums=range(5))(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for n, g in zip(INPUTS, grads):
        np.testing.assert_allclose(g, want_grads[n], rtol=1e-4, atol=1e-5)


def _lowered_for_tpu(f, *avals):
    from paddle_tpu.ops import pallas_kernels

    interpret, pallas_kernels._interpret = (pallas_kernels._interpret,
                                            lambda: False)
    jax.clear_caches()
    try:
        return jax.jit(f).trace(*avals).lower(
            lowering_platforms=("tpu",)).as_text()
    finally:
        pallas_kernels._interpret = interpret
        jax.clear_caches()


def test_lowered_for_a_tpu_the_op_is_six_mosaic_calls():
    """Compiled where interpreted here: forward + backward of the op at the
    cell's head shape (16 key heads under 32 value heads would be the
    same program: 2 under 4 here) lower to kernel 1 and the carry, then
    kernel 1 again, the carry's two backward walks and kernel 2, and no
    flag chose them; the decay reaches the inside's kernels as it came, a
    number a head a token, its chunks on the lanes ([B, Hv, N, 1, C]), and
    the carry's as one number a head a chunk ([N, B Hv, 1, 1]), never
    broadcast to the channels."""
    qk = jax.ShapeDtypeStruct((1, 2, 1024, 128), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 4, 1024, 128), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((1, 4, 1024), jnp.float32)
    text = _lowered_for_tpu(jax.value_and_grad(
        lambda *a: kda_ops.gdn_chunked(*a, 128 ** -0.5).astype(
            jnp.float32).sum(), argnums=range(5)), qk, qk, v, row, row)
    assert text.count("tpu_custom_call") == 6
    assert "stablehlo.while" not in text
    assert "tensor<1x4x16x1x64xf32>" in text
    assert "tensor<16x4x1x1xf32>" in text


def _half(t, kind):
    w = _data(t, kind)
    args = [jnp.asarray(w[n]) for n in INPUTS]
    return [a.astype(jnp.bfloat16) for a in args[:3]] + args[3:], w["mix"]


def test_bf16_operands_float32_state():
    """bf16 q, k, v with float32 g and beta: a bf16 result, within bf16
    rounding of the float32 recurrence on the same (rounded) inputs, and
    float32 gradients for g and beta."""
    half, _ = _half(130, "mixed")
    got = kda_ops.gdn_chunked(*half, SCALE)
    assert got.dtype == jnp.bfloat16
    want = recurrence(*[a.astype(jnp.float32) for a in half])
    assert np.abs(np.asarray(got, "float32") - np.asarray(want)).max() < 0.05
    grads = jax.grad(lambda *a: kda_ops.gdn_chunked(*a, SCALE).astype(
        jnp.float32).sum(), argnums=range(5))(*half)
    assert [str(g.dtype) for g in grads] == ["bfloat16"] * 3 + ["float32"] * 2


@functools.lru_cache(maxsize=None)
def _half_grads():
    half, mix = _half(200, "slow")
    return [jax.grad(lambda *a: (f(*a).astype(jnp.float32) * mix).sum(),
                     argnums=range(5))(*x)
            for f, x in ((lambda *a: kda_ops.gdn_chunked(*a, SCALE), half),
                         (recurrence, [a.astype(jnp.float32) for a in half]))]


@pytest.mark.parametrize("wrt", INPUTS)
def test_bf16_operands_every_gradient_is_the_recurrences(wrt):
    """Slow decays over four chunks, so that the entering states and the
    gradient through a chunk's whole decay count: every gradient within 2%
    of the largest element of the float32 recurrence's on the same
    (rounded) inputs."""
    got, want = (np.asarray(g[INPUTS.index(wrt)], "float32")
                 for g in _half_grads())
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 0.02 * np.abs(want).max()


# --- the carry's kernels under one decay a head -----------------------------
# (against the plain scan at random parts, both decays: tests/test_kda_op.py)
def test_the_carry_of_a_head_that_forgets_in_a_token_is_finite_and_unflushed():
    """g = -5 a token a head: the parts kernel 1 hands over have a chunk's
    whole decay of exp(-320) = 0.  The carry's result is the scan's,
    finite, the states it enters later chunks with are what the last
    tokens wrote, not the zero of a flushed state, and the gradients are
    finite."""
    w = _data(200, "all_fast")
    ins = tuple(kda_ops._whole_chunks(jnp.asarray(w[n]), 200)
                for n in INPUTS)
    parts = kda_ops._gdn_intra(ins, SCALE)
    assert float(jnp.abs(parts[5][:3]).max()) == 0.0  # the whole chunks
    got = kda_ops._carry_forward(parts, ins[2], 256)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, plain.scan_carry(parts), rtol=1e-4,
                               atol=1e-6)
    states, _ = kda_kernels.carry(parts, jnp.float32, B * HV, True)
    want = plain.scan_carry(parts, states=True)
    np.testing.assert_allclose(
        jnp.swapaxes(states, -1, -2).reshape(want.shape), want, rtol=1e-4,
        atol=1e-7)
    assert float(jnp.abs(states[1:]).max()) > 1e-3
    assert all(np.isfinite(g).all() for g in plain.kernel_grads(
        parts, plain.mix(parts)))


def test_the_carried_state_its_gradient_and_the_stacked_states_are_float32():
    """Under bf16 operands: each of the carry's three calls (the forward's
    walk, the backward's first walk, its reverse walk) holds its scratch
    float32, the backward's first walk stacks the entering states float32
    and U in the operands' dtype, and no scan is left in the op."""
    half, _ = _half(200, "slow")
    jaxpr = jax.make_jaxpr(jax.value_and_grad(lambda *a: kda_ops.gdn_chunked(
        *a, SCALE).astype(jnp.float32).sum(), argnums=range(5)))(*half)
    calls = plain.carry_calls(jaxpr)
    assert [len(c.outvars) for c in calls] == [1, 2, 6]
    for call in calls:
        (scratch,) = plain.scratch_avals(call)
        assert (scratch.shape, scratch.dtype) == ((B * HV, DV, DK),
                                                  jnp.float32)
    states, u = (v.aval for v in calls[1].outvars)
    assert (states.shape, states.dtype) == ((4, B * HV, DV, DK), jnp.float32)
    assert (u.shape, u.dtype) == ((4, B * HV, 64, DV), jnp.bfloat16)
    # the decay: one float32 number a head a chunk
    assert ((4, B * HV, 1, 1), jnp.float32) in [
        (v.aval.shape, v.aval.dtype) for v in calls[2].invars]
    assert not [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]


# --- through a Program ------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _program(t):
    w = _data(t)
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
        y = layers.gated_delta_attention(*ins)
        loss = layers.reduce_sum(layers.elementwise_mul(y, mix))
        fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        out = exe.run(main, feed=w, fetch_list=[y] + [
            main._grad_names[n] for n in INPUTS])
    return main, loss, y, out


def test_the_layer_builds_one_op_with_its_grad_op_and_it_verifies():
    main, loss, y, out = _program(65)
    types = [op.type for op in main.global_block().ops]
    assert types.count("gated_delta_attention") == 1
    assert types.count("gated_delta_attention_grad") == 1
    assert "kda_attention" not in types
    assert tuple(y.shape) == (B, HV, 65, DV) and str(y.dtype) == "float32"
    assert not [d for d in analysis.verify_program(main, fetches=[loss])
                if d.is_error]
    (got, grads), _ = _both(65, "mixed")
    np.testing.assert_allclose(out[0], got, rtol=1e-5, atol=1e-6)
    for n, g in zip(INPUTS, out[1:]):
        np.testing.assert_allclose(g, grads[n], rtol=1e-4, atol=1e-5)


def test_attribution_says_how_each_length_was_chunked_and_which_decay():
    kernel_tuning.reset_attribution()
    _program.cache_clear()
    _program(65)
    found = kernel_tuning.attribution()
    # the forward op and the grad op's lowering of it; KDA's record is its
    # own and stays empty
    # ..., the heads a grid step of the carry's kernels holds (all B Hv)
    assert found["gdn_chunks"] == {
        "ops": 2, "decay": "head",
        "lengths": {65: [64, 2, 65, 128, B * HV]}}
    assert found["kda_chunks"] == {"ops": 0, "lengths": {}}
    # kernel 1: the forward op, and the grad op twice (its forward, traced
    # and then dead, and its backward); kernel 2: the grad op; the carry,
    # the family's one, walks forward wherever kernel 1 ran and backwards
    # in the grad op
    hits = found["pallas_hits"]
    assert (hits["gdn_intra"], hits["gdn_intra_bwd"]) == (3, 1)
    assert (hits["kda_carry"], hits["kda_carry_bwd"]) == (3, 1)
    assert "kda_intra" not in hits


def test_amp_pass_narrows_q_k_v_and_keeps_the_decay_and_beta_float32():
    from paddle_tpu.transpiler.pass_registry import apply_pass

    shapes = {"Q": [B, HK, 70, DK], "K": [B, HK, 70, DK],
              "V": [B, HV, 70, DV], "G": [B, HV, 70], "Beta": [B, HV, 70]}
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        ins = [layers.data(n, shape=shapes[n], append_batch_size=False)
               for n in INPUTS]
        layers.gated_delta_attention(*ins)
        apply_pass(main, "bf16_amp_pass")
    block = main.global_block()
    (op,) = [o for o in block.ops if o.type == "gated_delta_attention"]
    dtypes = {slot: str(block.var(names[0]).dtype)
              for slot, names in list(op.inputs.items())
              + list(op.outputs.items())}
    assert dtypes == {"Q": "bfloat16", "K": "bfloat16", "V": "bfloat16",
                      "G": "float32", "Beta": "float32", "Out": "bfloat16"}


def _infer(q, v, beta, g=None, k=None):
    class Op:
        attrs = {}

    return get_infer_rule("gated_delta_attention").fn(Op, {
        "Q": [VarInfo(q, "bfloat16")], "K": [VarInfo(k or q, "bfloat16")],
        "V": [VarInfo(v, "bfloat16")], "G": [VarInfo(g or beta, "float32")],
        "Beta": [VarInfo(beta, "float32")]})


def test_infer_rule_gives_vs_shape_and_dtype():
    out = _infer((-1, 2, 70, 16), (-1, 4, 70, 8), (-1, 4, 70))["Out"][0]
    assert out.shape == (-1, 4, 70, 8) and out.dtype == "bfloat16"


@pytest.mark.parametrize("kwargs", [
    dict(q=(2, 2, 70, 16), v=(2, 4, 70, 8), beta=(2, 4, 71)),
    dict(q=(2, 2, 70, 16), v=(2, 4, 70, 8), beta=(2, 2, 70)),
    dict(q=(2, 3, 70, 16), v=(2, 4, 70, 8), beta=(2, 4, 70)),
    # a decay of every channel is kda_attention's
    dict(q=(2, 2, 70, 16), v=(2, 4, 70, 8), beta=(2, 4, 70),
         g=(2, 4, 70, 16)),
    dict(q=(2, 2, 70, 16), v=(2, 4, 70, 8), beta=(2, 4, 70),
         k=(2, 2, 70, 8)),
    dict(q=(2, 2, 70, 16), v=(2, 4, 71, 8), beta=(2, 4, 71)),
    dict(q=(4, 70, 16), v=(4, 70, 8), beta=(4, 70))])
def test_infer_rule_refuses_inconsistent_edges(kwargs):
    with pytest.raises(InferError, match="gated_delta_attention"):
        _infer(**kwargs)


def test_program_flops_counts_the_chunkwise_form_a_value_head():
    """A token a VALUE head 2 C (3 dk + 2 dv) + 6 dk dv, the grad op
    twice."""
    from paddle_tpu.utils.flops import program_flops

    main = _program(65)[0]
    one = B * HV * 65 * (2.0 * 64 * (3 * DK + 2 * DV) + 6.0 * DK * DV)
    assert program_flops(main) == 3.0 * one
