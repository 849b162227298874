"""kda_attention: Kimi Delta Attention's chunkwise lowering (ops/kda_ops.py,
its chunk inside the two Pallas kernels of ops/kda_kernels.py, interpreted
here) against the token-by-token recurrence it stands for, written here in
a lax.scan over T: the result and every input's gradient, at beta = 0 (pure
decay), g = 0 (the plain delta rule), log-decays down to -5 a token a
channel (exp(+320) over a chunk if it were ever taken) and so slow that a
chunk hands half its state on, lengths that pad (1, 63, 65, 200, 600) and
that do not (64), one chunk a grid step and several,
at the narrow heads of most cases and at the cell's (dk = dv = 128);
through a Program with its grad op, under the AMP pass, its infer rule,
its line in program_flops and what it leaves in attribution(); the
carry's kernels (kda_kernels.carry / carry_bwd, the state in a VMEM scratch
across a head's chunks) against the docstring's equations as a plain
lax.scan (tests/delta_rule_carry.py), under a per-channel decay and under
one a head (`gated_delta_attention`'s: the carry is the family's one); and
causal_conv, the ungated depthwise convolution beside short_conv, against
four shifted products."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, framework, layers, unique_name
from paddle_tpu.analysis.infer import InferError, VarInfo, get_infer_rule
from paddle_tpu.initializer import NumpyArrayInitializer
from paddle_tpu.ops import kda_kernels, kda_ops, kernel_tuning
from paddle_tpu.ops.nn_ops import causal_conv
from paddle_tpu.param_attr import ParamAttr

import delta_rule_carry as plain

B, H, DK, DV = 2, 2, 16, 8
SCALE = DK ** -0.5
INPUTS = ("Q", "K", "V", "G", "Beta")


def recurrence(q, k, v, g, beta, scale=SCALE):
    """S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
    v_t^T; o_t = S_t^T (scale q_t): one token a step, [B, H, T, .]."""
    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[..., None] * s
        u = bt[..., None] * (vt - jnp.einsum("bhc,bhcv->bhv", kt, s))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.einsum("bhc,bhcv->bhv", qt * scale, s)

    xs = [jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta)]
    _, o = jax.lax.scan(
        step, jnp.zeros(q.shape[:2] + (q.shape[-1], v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 2)


def _data(t, kind="mixed", dk=DK, dv=DV):
    """q and k on the unit sphere (as the model's L2 norm leaves them), v
    normal, beta in (0, 1), g by `kind`; `mix` weights the result so that
    the loss is no constant."""
    rng = np.random.RandomState(7 + t)
    q, k = (rng.randn(B, H, t, dk).astype("float32") for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -rng.uniform(0.001, 1.6, (B, H, t, dk)).astype("float32")
    beta = rng.uniform(0.05, 0.95, (B, H, t)).astype("float32")
    if kind == "pure_decay":
        beta = np.zeros_like(beta)
    elif kind == "no_decay":
        g = np.zeros_like(g)
    elif kind == "fast":  # half the channels forget in a token
        g = np.where(rng.rand(*g.shape) < 0.5, -5.0, g).astype("float32")
    elif kind == "all_fast":
        g = np.full_like(g, -5.0)
    elif kind == "slow":  # exp(G_C) ~ 0.5: the states reach far, and the
        g = g / 80.0      # chunk's whole decay has a gradient that counts
    return {"Q": q, "K": k, "V": rng.randn(B, H, t, dv).astype("float32"),
            "G": g, "Beta": beta,
            "mix": rng.uniform(0.5, 1.5, (B, H, t, dv)).astype("float32")}


@functools.lru_cache(maxsize=None)
def _both(t, kind, width=DK):
    """((result, gradients by input) of the op's lowering, the same of the
    recurrence); `width`: dk, and dv where it is not the narrow DK."""
    w = _data(t, kind, width, DV if width == DK else width)
    args = [jnp.asarray(w[n]) for n in INPUTS]
    scale = width ** -0.5
    out = []
    with jax.default_matmul_precision("highest"):
        for f in (lambda *a: kda_ops.kda_chunked(*a, scale),
                  lambda *a: recurrence(*a, scale)):
            o, pull = jax.jit(lambda *a: jax.vjp(f, *a))(*args)
            out.append((np.asarray(o), dict(zip(INPUTS, map(
                np.asarray, jax.jit(pull)(jnp.asarray(w["mix"])))))))
    return out


# every length with mixed decays; each special decay where a chunk is
# whole, where it pads and over several chunks (T = 200 is four chunks in
# one grid step, T = 600 ten, padded to two steps of eight); at the cell's
# head shape, dk = dv = 128, a length that pads and one that forgets in a
# token
CASES = ([(t, "mixed", DK) for t in (1, 63, 64, 65, 200, 600)]
         + [(65, "pure_decay", DK), (200, "pure_decay", DK),
            (64, "no_decay", DK), (200, "no_decay", DK), (65, "fast", DK),
            (200, "fast", DK), (63, "all_fast", DK), (200, "all_fast", DK),
            (200, "slow", DK), (130, "mixed", 128), (130, "fast", 128)])


@pytest.mark.parametrize("t, kind, width", CASES)
def test_the_chunkwise_result_is_the_recurrences(t, kind, width):
    (got, _), (want, _) = _both(t, kind, width)
    assert got.shape == want.shape == (B, H, t, DV if width == DK else width)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("wrt", INPUTS)
@pytest.mark.parametrize("t, kind, width", CASES)
def test_every_gradient_is_jax_grad_of_the_recurrence(t, kind, width, wrt):
    """The op's own backward (kernel 1 again for the carry's operands, the
    carry forward for the entering states and backwards, then the
    transposed inside by hand in kernel 2) against autodiff of the
    recurrence: 1e-4 of the gradient's largest element (measured: 1e-5 or
    less)."""
    (_, got), (_, want) = _both(t, kind, width)
    g, w = got[wrt], want[wrt]
    assert g.shape == w.shape and np.isfinite(g).all()
    assert np.abs(g - w).max() <= 1e-4 * max(np.abs(w).max(), 1e-3), wrt


def test_a_pure_decay_writes_nothing_and_a_first_token_reads_itself():
    """beta = 0: the state stays zero and so does the result, whatever
    the decay; at t = 0 the state is beta k v^T, so o_0 = beta (q.k) v."""
    (got, _), _ = _both(65, "pure_decay")
    assert np.abs(got).max() == 0.0
    w = _data(65, "mixed")
    (got, _), _ = _both(65, "mixed")
    first = (w["Beta"][..., 0, None] * SCALE
             * (w["Q"][..., 0, :] * w["K"][..., 0, :]).sum(-1, keepdims=True)
             * w["V"][..., 0, :])
    np.testing.assert_allclose(got[..., 0, :], first, rtol=1e-5, atol=1e-6)


def test_a_channel_that_forgets_in_a_token_gives_neither_inf_nor_a_flush():
    """g = -5 on every channel of every token: exp(+cumsum) would be
    exp(320) inside a chunk.  The result is finite and the recurrence's,
    and it is not the zero a flushed state would give: o_t is within 1% of
    what token t alone wrote (the rest decayed by exp(-5) a step)."""
    (got, grads), (want, _) = _both(200, "all_fast")
    assert np.isfinite(got).all()
    assert all(np.isfinite(g).all() for g in grads.values())
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    w = _data(200, "all_fast")
    own = (w["Beta"][..., None] * SCALE
           * (w["Q"] * w["K"]).sum(-1, keepdims=True) * w["V"])
    assert np.abs(got).max() > 0.1
    assert np.abs(got - own).max() < 0.02 * np.abs(own).max()


def test_output_at_t_does_not_see_inputs_after_t():
    w = _data(200)
    cut = 70
    later = {n: w[n].copy() for n in INPUTS}
    for n in ("Q", "V"):
        later[n][:, :, cut + 1:] += 3.0
    later["K"][:, :, cut + 1:] = _data(200, "fast")["Q"][:, :, cut + 1:]
    later["G"][:, :, cut + 1:] -= 1.0
    a, b = (np.asarray(kda_ops.kda_chunked(
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
        got = kda_ops.kda_chunked(*args, SCALE)
        grads = jax.grad(lambda *a: (kda_ops.kda_chunked(*a, SCALE)
                                     * w["mix"]).sum(),
                         argnums=range(5))(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for n, g in zip(INPUTS, grads):
        np.testing.assert_allclose(g, want_grads[n], rtol=1e-4, atol=1e-5)


def test_a_length_pads_to_whole_grid_steps():
    """Up to BLOCK chunks are one grid step of whole chunks; beyond, the
    length pads to whole steps of BLOCK chunks (no divisor is looked for:
    97 chunks are 13 steps of 8, not 97 of one)."""
    assert (kda_ops.CHUNK, kda_ops.BLOCK) == (64, 8)
    lengths = (1, 64, 65, 200, 512, 513, 600, 6144, 6208)
    assert [kda_ops._block(t) for t in lengths] == [1, 1, 2, 4, 8, 8, 8, 8, 8]
    assert [kda_ops._padded(t) for t in lengths] == [
        64, 64, 128, 256, 512, 1024, 1024, 6144, 6656]


def test_lowered_for_a_tpu_the_op_is_six_mosaic_calls(monkeypatch):
    """Compiled where interpreted here: forward + backward of the op at the
    cell's head shape lower to kernel 1 and the carry, then kernel 1 again,
    the carry's two backward walks and kernel 2, and no flag chose them;
    no loop is left beside them."""
    from paddle_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    jax.clear_caches()
    x = jax.ShapeDtypeStruct((1, 2, 1024, 128), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, 2, 1024, 128), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, 2, 1024), jnp.float32)
    text = jax.jit(jax.value_and_grad(
        lambda *a: kda_ops.kda_chunked(*a, 128 ** -0.5).astype(
            jnp.float32).sum(), argnums=range(5))).trace(
                x, x, x, g, beta).lower(lowering_platforms=("tpu",)).as_text()
    jax.clear_caches()
    assert text.count("tpu_custom_call") == 6
    assert "stablehlo.while" not in text


def _half(t, kind):
    w = _data(t, kind)
    args = [jnp.asarray(w[n]) for n in INPUTS]
    return [a.astype(jnp.bfloat16) for a in args[:3]] + args[3:], w["mix"]


def test_bf16_operands_float32_state():
    """bf16 q, k, v with float32 g and beta: a bf16 result, within bf16
    rounding of the float32 recurrence on the same (rounded) inputs, and
    float32 gradients for g and beta."""
    half, _ = _half(130, "mixed")
    got = kda_ops.kda_chunked(*half, SCALE)
    assert got.dtype == jnp.bfloat16
    want = recurrence(*[a.astype(jnp.float32) for a in half])
    assert np.abs(np.asarray(got, "float32") - np.asarray(want)).max() < 0.05
    grads = jax.grad(lambda *a: kda_ops.kda_chunked(*a, SCALE).astype(
        jnp.float32).sum(), argnums=range(5))(*half)
    assert [str(g.dtype) for g in grads] == ["bfloat16"] * 3 + ["float32"] * 2


@functools.lru_cache(maxsize=None)
def _half_grads():
    half, mix = _half(200, "slow")
    return [jax.grad(lambda *a: (f(*a).astype(jnp.float32) * mix).sum(),
                     argnums=range(5))(*x)
            for f, x in ((lambda *a: kda_ops.kda_chunked(*a, SCALE), half),
                         (recurrence, [a.astype(jnp.float32) for a in half]))]


@pytest.mark.parametrize("wrt", INPUTS)
def test_bf16_operands_every_gradient_is_the_recurrences(wrt):
    """Slow decays over four chunks, so that the entering states and the
    gradient through a chunk's whole decay count: every gradient within 2%
    of the largest element of the float32 recurrence's on the same
    (rounded) inputs (measured: 0.5-0.7%, the products' bf16 operands)."""
    got, want = (np.asarray(g[INPUTS.index(wrt)], "float32")
                 for g in _half_grads())
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 0.02 * np.abs(want).max()


def test_the_backward_keeps_the_entering_states_float32():
    """The state every chunk entered with is summed elementwise against
    the carried gradient (the gradient through exp(G_C)): under bf16
    operands the carry's kernels hold the state and its gradient float32
    in their scratch, and the backward's first walk stacks the entering
    states float32 as it carries them; only the products narrow them
    (rounding them where they are stacked moves dg by less than the
    products' own rounding, so no tolerance would say).  U, a product's
    operand, is stacked in the operands' dtype."""
    half, _ = _half(200, "slow")
    jaxpr = jax.make_jaxpr(jax.value_and_grad(lambda *a: kda_ops.kda_chunked(
        *a, SCALE).astype(jnp.float32).sum(), argnums=range(5)))(*half)
    calls = plain.carry_calls(jaxpr)
    # the forward's walk, the backward's first walk, its reverse walk
    assert [len(c.outvars) for c in calls] == [1, 2, 6]
    for call in calls:
        (scratch,) = plain.scratch_avals(call)
        assert (scratch.shape, scratch.dtype) == ((B * H, DV, DK),
                                                  jnp.float32)
    _, first, reverse = calls
    states, u = (v.aval for v in first.outvars)
    assert (states.shape, states.dtype) == ((4, B * H, DV, DK), jnp.float32)
    assert (u.shape, u.dtype) == ((4, B * H, 64, DV), jnp.bfloat16)
    assert states.shape in [v.aval.shape for v in reverse.invars]
    assert not [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]


# --- the carry's kernels against a plain scan -------------------------------
DECAYS = ("channel", "head")  # the chunk's whole decay [.., dk] or [.., 1]
# chunks, batch, heads, CARRY_HEADS (None: as the module has it)
CARRY_CASES = [(3, 1, 2, None), (1, 2, 2, None), (2, 2, 3, 4), (2, 1, 7, 4)]
CARRY_IDS = ["three_chunks", "one_chunk", "six_heads_three_a_step",
             "seven_heads_one_a_step"]


@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("n, b, h, heads", CARRY_CASES, ids=CARRY_IDS)
def test_the_carry_kernel_is_the_plain_scan(monkeypatch, n, b, h, heads,
                                            decay):
    """U = U0 - W S, O = (Q exp(G)) S + A_qk U, S' = gamma S + (K exp(G_C -
    G))^T U with the state in the kernel's scratch, against the same
    equations in a lax.scan, the chunk's whole decay a number a channel or
    ONE a head (the kernel broadcasts what it is given); a head count that
    CARRY_HEADS does not divide runs at its largest divisor (6 heads at 4:
    3; 7 at 4: one a step)."""
    if heads:
        monkeypatch.setattr(kda_ops, "CARRY_HEADS", heads)
        assert kda_ops._carry_heads(b * h) == {6: 3, 7: 1}[b * h]
    parts = plain.random_parts(n, b, h, decay, dk=DK, dv=DV)
    got = kda_ops._carry_forward(parts, plain.mix(parts), n * 64)
    assert got.shape == (b, h, n * 64, DV)
    np.testing.assert_allclose(got, plain.scan_carry(parts), rtol=1e-4,
                               atol=1e-5)


@functools.lru_cache(maxsize=None)
def _carry_grads(n, b, h, heads, decay):
    parts = plain.random_parts(n, b, h, decay, dk=DK, dv=DV)
    held, kda_ops.CARRY_HEADS = kda_ops.CARRY_HEADS, heads or \
        kda_ops.CARRY_HEADS
    try:
        return (plain.kernel_grads(parts, plain.mix(parts)),
                plain.scan_grads(parts, plain.mix(parts)))
    finally:
        kda_ops.CARRY_HEADS = held


@pytest.mark.parametrize("part", plain.PARTS)
@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("n, b, h, heads", CARRY_CASES, ids=CARRY_IDS)
def test_every_parts_gradient_through_the_carry_is_jax_grad_of_the_scan(
        n, b, h, heads, decay, part):
    """The backward's two walks (the entering states and U forward, then
    the state's gradient from the last chunk to the first, the three
    products that do not wait for it made in the same visit) against
    jax.grad of the plain scan: all six parts; one decay a head sums the
    kernel's [.., dk] over the channels, as `_gdn_bwd` does."""
    got, want = (g[plain.PARTS.index(part)] for g in _carry_grads(
        n, b, h, heads, decay))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("decay", DECAYS)
def test_the_carry_at_a_length_that_pads_reads_and_writes_t_tokens(decay):
    """101 tokens are two chunks a grid step (`_padded`: 128): the result
    is cut to 101 tokens, and its gradient comes 101 tokens long and pads
    with zeros, which is what the scan gets."""
    t = 101
    assert kda_ops._padded(t) == 128
    parts = plain.random_parts(2, B, H, decay, dk=DK, dv=DV, seed=1)
    mix = plain.mix(parts, t)
    got = kda_ops._carry_forward(parts, mix, t)
    assert got.shape == (B, H, t, DV)
    np.testing.assert_allclose(got, plain.scan_carry(parts)[:, :, :t],
                               rtol=1e-4, atol=1e-5)
    whole = jnp.pad(mix, [(0, 0), (0, 0), (0, 128 - t), (0, 0)])
    for g, w in zip(plain.kernel_grads(parts, mix),
                    plain.scan_grads(parts, whole)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_the_carry_of_a_head_that_forgets_in_a_token_is_finite_and_unflushed():
    """g = -5 a token a channel: the parts kernel 1 hands over have a
    chunk's whole decay of exp(-320) = 0 and K exp(G_C - G) that is 0 but
    for the chunk's last tokens.  The carry's result is the scan's, finite,
    and the states it enters later chunks with are what the last tokens
    wrote, not the zero of a flushed state; the gradients are finite."""
    w = _data(200, "all_fast")
    ins = tuple(kda_ops._whole_chunks(jnp.asarray(w[n]), 200)
                for n in INPUTS)
    parts = kda_ops._intra(ins, SCALE)
    assert float(jnp.abs(parts[5][:3]).max()) == 0.0  # the whole chunks
    got = kda_ops._carry_forward(parts, ins[2], 256)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, plain.scan_carry(parts), rtol=1e-4,
                               atol=1e-6)
    states, _ = kda_kernels.carry(parts, jnp.float32, B * H, True)
    want = plain.scan_carry(parts, states=True)
    np.testing.assert_allclose(
        jnp.swapaxes(states, -1, -2).reshape(want.shape), want, rtol=1e-4,
        atol=1e-7)
    assert float(jnp.abs(states[1:]).max()) > 1e-3
    assert all(np.isfinite(g).all() for g in plain.kernel_grads(
        parts, plain.mix(parts)))


def test_bf16_parts_carry_a_float32_state_through_the_kernel():
    """bf16 operands: the kernel's result is the scan's with the same
    operands narrowed and the state float32 (within the rounding of U,
    which the kernel narrows once for both of its products), in the
    result's dtype."""
    parts = plain.random_parts(4, B, H, "channel", jnp.bfloat16, DK, DV,
                               seed=2)
    got = kda_ops._carry_forward(
        parts, jax.ShapeDtypeStruct((B, H, 256, DV), jnp.bfloat16), 256)
    assert got.dtype == jnp.bfloat16
    want = plain.scan_carry(parts, jnp.bfloat16)
    assert np.abs(np.asarray(got, "float32") - want).max() \
        <= 0.02 * np.abs(want).max()


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
        y = layers.kda_attention(*ins)
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
    assert types.count("kda_attention") == 1
    assert types.count("kda_attention_grad") == 1
    assert tuple(y.shape) == (B, H, 65, DV) and str(y.dtype) == "float32"
    assert not [d for d in analysis.verify_program(main, fetches=[loss])
                if d.is_error]
    (got, grads), _ = _both(65, "mixed")
    np.testing.assert_allclose(out[0], got, rtol=1e-5, atol=1e-6)
    for n, g in zip(INPUTS, out[1:]):
        np.testing.assert_allclose(g, grads[n], rtol=1e-4, atol=1e-5)


def test_attribution_says_how_each_length_was_chunked():
    kernel_tuning.reset_attribution()
    _program.cache_clear()
    _program(65)
    found = kernel_tuning.attribution()["kda_chunks"]
    # the forward op and the grad op's lowering of it
    assert found["ops"] == 2
    # ..., the heads a grid step of the carry's kernels holds (all B H = 4)
    assert found["lengths"] == {65: [64, 2, 65, 128, 4]}
    # kernel 1: the forward op, and the grad op twice (its forward, traced
    # and then dead, and its backward); kernel 2: the grad op; the carry
    # walks forward wherever kernel 1 ran, and backwards in the grad op
    hits = kernel_tuning.attribution()["pallas_hits"]
    assert (hits["kda_intra"], hits["kda_intra_bwd"]) == (3, 1)
    assert (hits["kda_carry"], hits["kda_carry_bwd"]) == (3, 1)


def test_amp_pass_narrows_q_k_v_and_keeps_the_decay_and_beta_float32():
    from paddle_tpu.transpiler.pass_registry import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        ins = [layers.data(n, shape=[B, H, 70] + ([] if n == "Beta" else
                                                  [DV if n == "V" else DK]),
                           append_batch_size=False) for n in INPUTS]
        layers.kda_attention(*ins)
        apply_pass(main, "bf16_amp_pass")
    block = main.global_block()
    (op,) = [o for o in block.ops if o.type == "kda_attention"]
    dtypes = {slot: str(block.var(names[0]).dtype)
              for slot, names in list(op.inputs.items())
              + list(op.outputs.items())}
    assert dtypes == {"Q": "bfloat16", "K": "bfloat16", "V": "bfloat16",
                      "G": "float32", "Beta": "float32", "Out": "bfloat16"}


def _infer(q, v, beta, g=None, k=None):
    class Op:
        attrs = {}

    return get_infer_rule("kda_attention").fn(Op, {
        "Q": [VarInfo(q, "bfloat16")], "K": [VarInfo(k or q, "bfloat16")],
        "V": [VarInfo(v, "bfloat16")], "G": [VarInfo(g or q, "float32")],
        "Beta": [VarInfo(beta, "float32")]})


def test_infer_rule_gives_vs_shape_and_dtype():
    out = _infer((-1, 4, 70, 16), (-1, 4, 70, 8), (-1, 4, 70))["Out"][0]
    assert out.shape == (-1, 4, 70, 8) and out.dtype == "bfloat16"


@pytest.mark.parametrize("kwargs", [
    dict(q=(2, 4, 70, 16), v=(2, 4, 70, 8), beta=(2, 4, 71)),
    dict(q=(2, 4, 70, 16), v=(2, 4, 70, 8), beta=(2, 4, 70, 1)),
    dict(q=(2, 4, 70, 16), v=(2, 5, 70, 8), beta=(2, 4, 70)),
    dict(q=(2, 4, 70, 16), v=(2, 4, 70, 8), beta=(2, 4, 70),
         g=(2, 4, 70)),
    dict(q=(2, 4, 70, 16), v=(2, 4, 70, 8), beta=(2, 4, 70),
         k=(2, 4, 70, 8)),
    dict(q=(4, 70, 16), v=(4, 70, 8), beta=(4, 70))])
def test_infer_rule_refuses_inconsistent_edges(kwargs):
    with pytest.raises(InferError, match="kda_attention"):
        _infer(**kwargs)


def test_program_flops_counts_the_chunkwise_form():
    """A token a head 2 C (3 dk + 2 dv) + 6 dk dv, the grad op twice."""
    from paddle_tpu.utils.flops import program_flops

    main = _program(65)[0]
    one = B * H * 65 * (2.0 * 64 * (3 * DK + 2 * DV) + 6.0 * DK * DV)
    assert program_flops(main) == 3.0 * one


# --- causal_conv ------------------------------------------------------------
T, D = 10, 8


def shifted_products(x, filt, silu):
    """c_t = sum_j filt[:, j] x_{t-(L-1)+j}, zeros left of t = 0, as L
    shifted products; SiLU where said."""
    taps, t = filt.shape[1], x.shape[-2]
    c = 0.0 * x
    for j in range(taps):
        back = min(taps - 1 - j, t)
        c = c + jnp.concatenate(
            [jnp.zeros_like(x[..., :back, :]), x[..., :t - back, :]],
            -2) * filt[:, j]
    return jax.nn.silu(c) if silu else c


def _conv_data(taps, rank):
    rng = np.random.RandomState(5 + taps)
    shape = (3, T, D) if rank == 3 else (T, D)
    return {"x": rng.randn(*shape).astype("float32"),
            "filt": rng.randn(D, taps).astype("float32"),
            "mix": rng.uniform(0.5, 1.5, shape).astype("float32")}


@functools.lru_cache(maxsize=None)
def _conv_run(taps, rank, act):
    w = _conv_data(taps, rank)
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("x", shape=list(w["x"].shape),
                        append_batch_size=False)
        x.stop_gradient = False
        mix = layers.data("mix", shape=list(w["mix"].shape),
                          append_batch_size=False)
        y = layers.causal_conv(x, taps, act=act, param_attr=ParamAttr(
            name="filt", initializer=NumpyArrayInitializer(w["filt"])))
        loss = layers.reduce_sum(layers.elementwise_mul(y, mix))
        fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        out = exe.run(main, feed={"x": w["x"], "mix": w["mix"]},
                      fetch_list=[y, main._grad_names["x"],
                                  main._grad_names["filt"]])
    errors = [d for d in analysis.verify_program(main, fetches=[loss])
              if d.is_error]
    silu = act == "silu"
    args = (jnp.asarray(w["x"]), jnp.asarray(w["filt"]))
    want = shifted_products(*args, silu)
    grads = jax.grad(lambda a, k: (shifted_products(a, k, silu)
                                   * w["mix"]).sum(), argnums=(0, 1))(*args)
    return out, errors, (want,) + grads, y


CONV_CASES = [(4, 3, "silu"), (4, 2, "silu"), (4, 3, None), (3, 3, "silu"),
              (1, 3, "silu")]


@pytest.mark.parametrize("taps, rank, act", CONV_CASES)
def test_causal_conv_is_the_shifted_products(taps, rank, act):
    got, errors, want, y = _conv_run(taps, rank, act)
    assert got[0].shape == want[0].shape == tuple(y.shape)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=1e-6)
    assert not errors


@pytest.mark.parametrize("wrt", ["X", "Filter"])
@pytest.mark.parametrize("taps, rank, act", CONV_CASES)
def test_causal_convs_gradient_is_jax_grad_of_the_shifted_products(
        taps, rank, act, wrt):
    got, _, want, _ = _conv_run(taps, rank, act)
    i = 1 if wrt == "X" else 2
    assert got[i].shape == want[i].shape
    np.testing.assert_allclose(got[i], want[i], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cut", [0, 4, T - 2])
def test_causal_conv_at_t_does_not_see_inputs_after_t(cut):
    w = _conv_data(4, 3)
    later = w["x"].copy()
    later[:, cut + 1:] += 7.0
    a, b = (np.asarray(causal_conv(jnp.asarray(x), jnp.asarray(w["filt"]),
                                   True)) for x in (w["x"], later))
    np.testing.assert_array_equal(a[:, :cut + 1], b[:, :cut + 1])
    assert np.abs(a[:, cut + 1] - b[:, cut + 1]).max() > 0.5


def test_causal_conv_bf16_operands_float32_arithmetic():
    w = _conv_data(4, 3)
    x16 = jnp.asarray(w["x"]).astype(jnp.bfloat16)
    filt = jnp.asarray(w["filt"])
    got = causal_conv(x16, filt, True)
    assert got.dtype == jnp.bfloat16
    want = shifted_products(x16.astype(jnp.float32), filt, True).astype(
        jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got, "float32"),
                                  np.asarray(want, "float32"))


def test_amp_pass_runs_causal_conv_on_bf16_activations():
    from paddle_tpu.transpiler.pass_registry import apply_pass

    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("x", shape=[T, D], dtype="float32")
        h = layers.fc(x, size=2 * D, num_flatten_dims=2, bias_attr=False)
        y = layers.causal_conv(h, 4, act="silu")
        layers.fc(y, size=D, num_flatten_dims=2, bias_attr=False)
        apply_pass(main, "bf16_amp_pass")
    block = main.global_block()
    (op,) = [o for o in block.ops if o.type == "causal_conv"]
    dtypes = {slot: str(block.var(names[0]).dtype)
              for slot, names in list(op.inputs.items())
              + list(op.outputs.items())}
    assert dtypes == {"X": "bfloat16", "Filter": "float32",
                      "Out": "bfloat16"}


def test_causal_conv_refuses_what_it_does_not_compute():
    with pytest.raises(ValueError, match="causal_conv"):
        layers.causal_conv(None, 4, act="relu")

    class Op:
        attrs = {}

    rule = get_infer_rule("causal_conv").fn
    out = rule(Op, {"X": [VarInfo((-1, 32, D), "bfloat16")],
                    "Filter": [VarInfo((D, 4), "float32")]})["Out"][0]
    assert out.shape == (-1, 32, D) and out.dtype == "bfloat16"
    with pytest.raises(InferError, match="causal_conv"):
        rule(Op, {"X": [VarInfo((4, 32, D), "float32")],
                  "Filter": [VarInfo((D + 1, 4), "float32")]})


# --- causal_conv with a bias (Mamba-2's `use_conv_bias`, PR 57) --------------
@functools.lru_cache(maxsize=None)
def _conv_bias_run(act):
    w = _conv_data(4, 3)
    bias = np.random.RandomState(9).randn(D).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("x", shape=list(w["x"].shape),
                        append_batch_size=False)
        x.stop_gradient = False
        mix = layers.data("mix", shape=list(w["mix"].shape),
                          append_batch_size=False)
        y = layers.causal_conv(
            x, 4, act=act,
            param_attr=ParamAttr(
                name="filt", initializer=NumpyArrayInitializer(w["filt"])),
            bias_attr=ParamAttr(
                name="bias", initializer=NumpyArrayInitializer(bias)))
        loss = layers.reduce_sum(layers.elementwise_mul(y, mix))
        fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        out = exe.run(main, feed={"x": w["x"], "mix": w["mix"]},
                      fetch_list=[y] + [main._grad_names[n]
                                        for n in ("x", "filt", "bias")])
    errors = [d for d in analysis.verify_program(main, fetches=[loss])
              if d.is_error]

    def plain(a, k, b):
        c = shifted_products(a, k, False) + b
        return jax.nn.silu(c) if act == "silu" else c

    args = (jnp.asarray(w["x"]), jnp.asarray(w["filt"]), jnp.asarray(bias))
    grads = jax.grad(lambda *a: (plain(*a) * w["mix"]).sum(),
                     argnums=(0, 1, 2))(*args)
    return out, errors, (plain(*args),) + grads, main


@pytest.mark.parametrize("act", ["silu", None])
def test_causal_conv_adds_its_bias_before_the_activation(act):
    """Result and the three gradients (x, the filter, the bias) against the
    shifted products plus the bias, written out; the bias is a [d]
    parameter, zero where no initializer says otherwise, and an op built
    without `bias_attr` has no `Bias` slot (the accepted cells' op)."""
    got, errors, want, main = _conv_bias_run(act)
    assert not errors
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    (op,) = [o for o in main.global_block().ops if o.type == "causal_conv"]
    assert tuple(main.global_block().var(op.inputs["Bias"][0]).shape) == (D,)
    plain_main = _conv_run(4, 3, act)[3].block.program
    (plain_op,) = [o for o in plain_main.global_block().ops
                   if o.type == "causal_conv"]
    assert "Bias" not in plain_op.inputs


def test_causal_conv_infer_rule_checks_the_bias():
    rule = get_infer_rule("causal_conv").fn

    class Op:
        attrs = {"act": "silu"}

    ins = {"X": [VarInfo((2, 10, 8), "bfloat16")],
           "Filter": [VarInfo((8, 4), "float32")]}
    assert rule(Op, dict(ins, Bias=[VarInfo((8,), "float32")]))[
        "Out"][0].shape == (2, 10, 8)
    with pytest.raises(InferError, match=r"causal_conv Bias\(4,\) is not"):
        rule(Op, dict(ins, Bias=[VarInfo((4,), "float32")]))
