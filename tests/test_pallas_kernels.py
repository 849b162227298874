"""Pallas kernel library: flash attention vs dense XLA references (forward
and gradients), the tiled vocabulary head, and the cross-lowering of every
kernel for the TPU.  Runs in interpreter mode on the CPU mesh; the same
kernels compile on TPU."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.ops.pallas_kernels import (_dense_attention, flash_attention,
                                           short_attention)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_dense(causal):
    rng = np.random.RandomState(0)
    bh, t, d = 4, 32, 16
    q = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    k = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    v = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    scale = 1.0 / np.sqrt(d)
    out = flash_attention(q, k, v, None, causal, scale, 8, 8)
    ref = _dense_attention(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)

    # key-padding bias path: mask out the tail keys of each row
    kbias = np.zeros((bh, t), "float32")
    kbias[:, t - 5:] = -1e9
    kbias = jnp.asarray(kbias)
    out_b = flash_attention(q, k, v, kbias, causal, scale, 8, 8)
    ref_b = _dense_attention(q, k, v, causal, scale, kbias)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(ref_b), rtol=2e-4, atol=2e-5)
    # masked keys must not influence the output: perturbing them is a no-op
    v_pert = v.at[:, t - 5:, :].add(7.0)
    out_p = flash_attention(q, k, v_pert, kbias, causal, scale, 8, 8)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_b), rtol=2e-4, atol=2e-5)


def test_flash_attention_grads_match_dense():
    rng = np.random.RandomState(1)
    bh, t, d = 2, 16, 8
    q = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    k = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    v = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    scale = 1.0 / np.sqrt(d)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, True, scale, 8, 8) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, True, scale) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


def test_fused_attention_op_dispatch_and_training(monkeypatch):
    """The fused_attention layer trains identically through the blockwise
    kernel and the dense lowering.  The training path reads no flag: the
    test says what platform and shape would (nn_ops._flash_engages)."""
    from paddle_tpu.ops import kernel_tuning as kt
    from paddle_tpu.ops import nn_ops

    rng = np.random.RandomState(3)
    xv = rng.rand(4, 2, 16, 8).astype("float32")

    def run(engage):
        monkeypatch.setattr(nn_ops, "_flash_engages",
                            lambda ctx, tq, tk, d, dv=None: engage)
        import paddle_tpu.framework as fw
        from paddle_tpu.core import scope as scope_mod
        from paddle_tpu import unique_name

        fw.switch_main_program(fluid.Program())
        fw.switch_startup_program(fluid.Program())
        unique_name.switch()
        scope_mod._switch_scope(scope_mod.Scope())
        fluid.default_main_program().random_seed = 3
        fluid.default_startup_program().random_seed = 3

        q = layers.data("q", shape=[2, 16, 8])
        att = layers.fused_attention(q, q, q, causal=True)
        loss = layers.mean(layers.pow(att, 2.0))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        (lv,) = exe.run(feed={"q": xv}, fetch_list=[loss])
        return float(np.ravel(lv)[0])

    plain = run(False)
    before = kt.attribution()["pallas_hits"].get("attention", 0)
    pallas = run(True)
    assert kt.attribution()["pallas_hits"]["attention"] > before
    np.testing.assert_allclose(pallas, plain, rtol=1e-4)


def test_flash_attention_bf16_inputs():
    """bf16 q/k/v (the on-TPU AMP regime): kernel accumulates in f32 and
    matches the dense reference at bf16 tolerance, output dtype preserved."""
    rng = np.random.RandomState(5)
    bh, t, d = 2, 16, 8
    mk = lambda s: jnp.asarray(rng.randn(bh, t, d).astype("float32")).astype(
        jnp.bfloat16)
    q, k, v = mk(1), mk(2), mk(3)
    out = flash_attention(q, k, v, None, True, None, 8, 8)
    assert out.dtype == jnp.bfloat16
    ref = _dense_attention(q, k, v, True, 1.0 / np.sqrt(d))
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_flash_attention_kbias_grad_matches_dense():
    """The blocked dkbias kernel output matches the dense vjp (the key-bias
    grad previously came from dense recompute; now it is accumulated in the
    dk/dv pallas pass)."""
    rng = np.random.RandomState(6)
    bh, t, d = 2, 16, 8
    q = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    k = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    v = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    kbias = jnp.asarray((rng.randn(bh, t) * 0.5).astype("float32"))
    scale = 1.0 / np.sqrt(d)

    def loss_flash(q, k, v, kb):
        return jnp.sum(flash_attention(q, k, v, kb, False, scale, 8, 8) ** 2)

    def loss_dense(q, k, v, kb):
        return jnp.sum(_dense_attention(q, k, v, False, scale, kb) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, kbias)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2, 3))(q, k, v, kbias)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_multiblock_grid_grads(causal):
    """T=256 with 128-blocks: a real multi-cell (2x2) grid through both the
    fwd scratch carry and both backward kernels."""
    rng = np.random.RandomState(7)
    bh, t, d = 1, 256, 16
    q = jnp.asarray(rng.randn(bh, t, d).astype("float32") * 0.5)
    k = jnp.asarray(rng.randn(bh, t, d).astype("float32") * 0.5)
    v = jnp.asarray(rng.randn(bh, t, d).astype("float32") * 0.5)
    scale = 1.0 / np.sqrt(d)

    out = flash_attention(q, k, v, None, causal, scale)
    ref = _dense_attention(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)

    gf = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, None, causal, scale) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(
        lambda q, k, v: jnp.sum(
            _dense_attention(q, k, v, causal, scale) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_flash_attention_piece_merge_matches_full():
    """flash_attention_piece: two half-K/V pieces merged by logsumexp equal
    full attention (the ring-attention chunk contract)."""
    from paddle_tpu.ops.pallas_kernels import flash_attention_piece

    rng = np.random.RandomState(8)
    bh, t, d = 2, 32, 8
    q = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    k = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    v = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    scale = 1.0 / np.sqrt(d)
    h = t // 2

    o1, lse1 = flash_attention_piece(q, k[:, :h], v[:, :h], False,
                                     scale, 8, 8)
    o2, lse2 = flash_attention_piece(q, k[:, h:], v[:, h:], False,
                                     scale, 8, 8)
    lse = jnp.logaddexp(lse1, lse2)
    merged = (o1 * jnp.exp(lse1 - lse)[..., None]
              + o2 * jnp.exp(lse2 - lse)[..., None])
    ref = _dense_attention(q, k, v, False, scale)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window", [4, 16])
def test_flash_attention_sliding_window_matches_dense(window):
    """window attention: values and grads match the dense banded-mask
    reference; out-of-window blocks are skipped (Mistral-style SWA)."""
    rng = np.random.RandomState(11)
    bh, t, d = 2, 32, 8
    q = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    k = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    v = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    scale = 1.0 / np.sqrt(d)

    out = flash_attention(q, k, v, None, True, scale, 8, 8, window)
    ref = _dense_attention(q, k, v, True, scale, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)

    gf = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, None, True, scale, 8, 8, window) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(
        _dense_attention(q, k, v, True, scale, window=window) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def _masked_softmax_attention(q, k, v, scale, window):
    """The dense mask written from positions, apart from
    `_dense_attention`: key j visible to query i iff 0 <= i - j < window
    (window 0: plain causal)."""
    t = q.shape[1]
    dist = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    keep = (dist >= 0) & ((dist < window) if window else True)
    s = jnp.where(keep[None], jnp.einsum("bqd,bkd->bqk", q, k) * scale,
                  -jnp.inf)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("backward", ["one_kernel", "two_kernels"])
@pytest.mark.parametrize("window", [5, 12, 27, 32, 40])
def test_flash_window_any_length_matches_the_mask_from_positions(
        monkeypatch, window, backward):
    """T = 32 in blocks of 8 under windows that are no multiple of a block
    (5, 12, 27: T is no multiple of them either), the whole length (32)
    and beyond it (40): the forward kernel and both backward forms (the
    one-kernel backward a training step takes up to T = 8192 at 128, the
    dq and dk/dv kernels beyond) against a softmax under the mask built
    from positions; a window that reaches every key IS full causal
    attention, to the bit."""
    from paddle_tpu.ops import pallas_kernels as pk

    if backward == "two_kernels":
        monkeypatch.setattr(pk, "_FUSED_BWD_DQ_BYTES", 0)
    jax.clear_caches()
    rng = np.random.RandomState(13)
    bh, t, d = 2, 32, 8
    q, k, v = (jnp.asarray(rng.randn(bh, t, d).astype("float32"))
               for _ in range(3))
    scale = 1.0 / np.sqrt(d)

    def loss(fn):
        w = jnp.cos(jnp.arange(bh * t * d, dtype=jnp.float32)).reshape(
            bh, t, d)
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    kernel = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, None, True, scale, 8, 8, window)
    mask = lambda q, k, v: _masked_softmax_attention(  # noqa: E731
        q, k, v, scale, window)
    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(mask(q, k, v)),
                               rtol=2e-4, atol=2e-5)
    got = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(mask), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)
    if window >= t:
        full = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, None, True, scale, 8, 8, 0)
        np.testing.assert_array_equal(np.asarray(kernel(q, k, v)),
                                      np.asarray(full(q, k, v)))
        for a, b in zip(got, jax.grad(loss(full), argnums=(0, 1, 2))(q, k,
                                                                     v)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    jax.clear_caches()  # the patched limit must not outlive the test


def test_band_grid_at_the_cells_shape():
    """Trinity-Mini's window layers: 3 of 8 k blocks a q block, 24 steps
    a head where the full grid walked 64, 21 of them computed."""
    from paddle_tpu.ops import pallas_kernels as pk

    assert pk._band_grid(8192, 8192, 1024, 1024, True, 2048) == 3
    assert pk._band_grid(8192, 8192, 1024, 1024, True, 2048, True) == 3
    assert pk.band_grid_steps(8192, 1024, 1024, 2048) == (24, 21)
    assert pk.band_grid_steps(8192, 512, 512, 2048) == (80, 70)
    assert pk.band_grid_steps(8192, 1024, 1024, 8192) == (64, 36)


def _pallas_eqns(jaxpr):
    """Every pallas_call equation under `jaxpr`, nested calls included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_eqns(sub)
    return found


@pytest.mark.parametrize("backward", ["one_kernel", "two_kernels"])
def test_without_a_window_the_traced_kernels_are_the_full_grid(monkeypatch,
                                                               backward):
    """window == 0 (and a window that covers the sequence) trace the
    (BH, nq, nk) / (BH, nk, nq) grids and kernel bodies with no division or
    minimum, as before the band grid.  Their index maps compute on the
    INNER block's operands alone (PR 56: k, v and the key rows where k is
    innermost; q, do, lse and delta where q is: a step the mask skips names
    the block of the next live one) and hand a grid index on for every
    other operand and every result; a non-causal call, and a causal one
    whose inner axis is one step, hand the step on everywhere.  A window
    inside the sequence traces the band's width and maps that compute.
    (The Mosaic modules themselves are pinned in
    tests/test_lowered_step_pins.py.)"""
    from paddle_tpu.ops import pallas_kernels as pk

    if backward == "two_kernels":
        monkeypatch.setattr(pk, "_FUSED_BWD_DQ_BYTES", 0)
    jax.clear_caches()
    bh, t, d, blk = 2, 64, 8, 8
    x = jnp.zeros((bh, t, d), jnp.float32)

    def calls(window, causal=True, blk=blk):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, None, causal, 1.0, blk, blk, window)),
            argnums=(0, 1, 2)))(x, x, x)
        return _pallas_eqns(jaxpr.jaxpr)

    def arithmetic(eqn):
        """(operands whose index map computes, results whose map does, has
        the body a div or a min)"""
        maps = {bm.origin: len(bm.index_map_jaxpr.jaxpr.eqns)
                for bm in eqn.params["grid_mapping"].block_mappings}
        body = {e.primitive.name for e in eqn.params["jaxpr"].eqns}
        return (sorted(o for o, n in maps.items() if n and "args" in o),
                sorted(o for o, n in maps.items() if n and "args" not in o),
                bool(body & {"div", "min"}))

    # q, k, v, key bias (the forward and the two-kernel backward carry a
    # zero one), do, lse, delta
    k_side = ["args[1]", "args[2]", "args[3]"]
    forward = (k_side, [], False)
    backward_kernels = {
        "one_kernel": [(["args[0]", "args[3]", "args[4]", "args[5]"], [],
                        False)],
        "two_kernels": [(k_side, [], False),
                        (["args[0]", "args[4]", "args[5]", "args[6]"], [],
                         False)]}[backward]
    n = t // blk
    for window in (0, t, t + 5):
        got = calls(window)
        assert len(got) == (2 if backward == "one_kernel" else 3)
        for eqn in got:
            assert eqn.params["grid_mapping"].grid == (bh, n, n)
        assert [arithmetic(eqn) for eqn in got] == [forward] \
            + backward_kernels
    for got in (calls(0, causal=False), calls(0, blk=t)):
        assert len(got) == (2 if backward == "one_kernel" else 3)
        for eqn in got:
            assert arithmetic(eqn) == ([], [], False)
    got = calls(2 * blk)  # 3 blocks a walk: its own, two of the window
    for eqn in got:
        assert eqn.params["grid_mapping"].grid == (bh, n, 3)
        operands, results, body = arithmetic(eqn)
        assert operands and not results and body
    jax.clear_caches()


# (name, window as a function of (T, block)): the ways a causal band lies
# over the tiles
BANDS = {
    "causal": lambda t, blk: 0,
    "window_inside": lambda t, blk: (2 * blk if t > 2 * blk else blk
                                     if t > blk else t - 3),
    "window_inside_off_block": lambda t, blk: blk + blk // 2 - 3,
    "window_covers": lambda t, blk: t + 5,
    "window_narrower_than_a_block": lambda t, blk: blk // 2 + 3,
}


@pytest.mark.parametrize("extras", ["plain", "kbias_and_segments"])
@pytest.mark.parametrize("blk,blocks,d,dv", [
    (128, 1, 64, 64), (256, 1, 64, 64), (256, 3, 64, 64), (512, 2, 64, 64),
    (128, 1, 192, 128), (256, 1, 192, 128), (256, 3, 192, 128)])
@pytest.mark.parametrize("band", sorted(BANDS))
def test_a_tile_by_where_it_lies_is_the_whole_masked_tile(
        monkeypatch, band, blk, blocks, d, dv, extras):
    """The training path's forward and one-kernel backward, a tile computed
    by its class (whole without a mask, cut tiles in strips over their
    visible part: pallas_kernels._tile_plan), against every tile computed
    whole under the mask from positions (the plan held at none), both
    interpreted: o, lse, dk, dv (and the key bias's gradient) to float32
    rounding of the sums' order (a strip's exact zeros are left out of a
    row's sum and of a matmul's contraction), dq alike."""
    from paddle_tpu.ops import pallas_kernels as pk

    t = blk * blocks
    window = BANDS[band](t, blk)
    rng = np.random.RandomState(blk + blocks + d)
    q, k, do = (jnp.asarray(rng.randn(1, t, n).astype("float32"))
                for n in (d, d, dv))
    v = jnp.asarray(rng.randn(1, t, dv).astype("float32"))
    kbias = seg = None
    if extras != "plain":
        kb = np.zeros((1, t), "float32")
        kb[:, 5:9] = -1e9  # padded keys inside the first strip
        kb[:, t - 70:t - 30] = -3.0
        kbias = jnp.asarray(kb)
        seg = jnp.asarray((np.arange(t) >= t // 3 + 5).astype(
            "int32")[None, :])
    scale = d ** -0.5

    def both_passes():
        kb = kbias if kbias is not None else jnp.zeros((1, t), jnp.float32)
        o, lse = pk._flash_fwd(q, k, v, kb, True, scale, blk, blk, window,
                               seg=seg, interpret=True, by_class=True)
        grads = pk._flash_bwd_fused(q, k, v, kbias, seg, o, lse, do, True,
                                    scale, blk, blk, window, True)
        return (o, lse) + tuple(g for g in grads if g is not None)

    plan = pk._tile_plan(t, blk, blk, window, pk._strip_parts(blk))
    assert plan.bodies <= (9 if window else 5)
    if blk >= 256 and band in ("causal", "window_covers"):
        assert plan.diag == blk // 128 and not plan.edge and not plan.both
    if band == "window_narrower_than_a_block":
        assert plan.both and plan.diag == 0 and plan.edge <= 1
    if band == "window_inside" and blocks > 1 and blk >= 256:
        assert plan.diag == plan.edge == min(4, blk // 128)
        assert plan.whole == (blocks > 2)
    if band == "window_inside_off_block" and blocks > 1 and blk >= 256:
        assert plan.diag > 1 and plan.edge == 1  # no corner-to-corner cut
    by_class = both_passes()
    monkeypatch.setattr(pk, "_tile_plan", lambda *a, **kw: None)
    masked = both_passes()
    assert len(by_class) == len(masked) == (5 if kbias is None else 6)
    for a, b in zip(by_class, masked):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)
    # and the masked computation is the dense one
    ref = _dense_attention(q, k, v, True, scale, kbias, window=window,
                           seg=seg)
    np.testing.assert_allclose(np.asarray(by_class[0]), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def _dots(jaxpr):
    """dot_general equations under `jaxpr`, every branch included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "dot_general"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _dots(sub)
    return n


# name -> (T, d, d_v, window, block, copies of the tile's computation the
# forward's and the backward's body may hold); the cells' cores first
BODIES = {
    "gpt2": (1024, 64, 64, 0, 1024, 1, 4),
    "ouro_olmoe": (4096, 128, 128, 0, 1024, 3, 5),
    "kanana2_kimi": (6144, 192, 128, 0, 1024, 3, 5),
    "lfm2": (8192, 64, 64, 0, 1024, 3, 5),
    "qwen3_next": (8192, 256, 256, 0, 1024, 3, 5),
    "trinity_window": (8192, 128, 128, 2048, 1024, 5, 9),
    "window_covers": (8192, 128, 128, 8192, 1024, 3, 5),
    "window_off_block": (8192, 128, 128, 2500, 1024, 4, 6),
    "window_narrower_than_a_block": (8192, 128, 128, 1000, 1024, 1, 1),
    "blocks_of_128": (512, 64, 64, 0, 128, 2, 2),
}


@pytest.mark.parametrize("name", sorted(BODIES))
def test_a_kernel_body_holds_no_more_copies_than_its_cut_edges_allow(name):
    """What a model's first step pays once a kernel is the size of ONE
    traced body (~0.05 s a copy of the tile's computation, PERF.md section
    6): 1 + 4 copies a cut edge at most (causal 5, windowed 9; the parent
    held 1), counted as dot_generals of the traced kernel (two a copy in
    the forward, five in the one-kernel backward); the forward takes a cut
    tile in two strips, so 1 + 2 an edge, and GPT-2's one tile a head
    whole (pallas_kernels._fwd_strip_parts).  What tile_class_stats tells
    the benchmark is what was traced."""
    from paddle_tpu.ops import pallas_kernels as pk

    t, d, dv, window, blk, fwd_copies, bwd_copies = BODIES[name]
    x = jax.ShapeDtypeStruct((2, t, d), jnp.bfloat16)
    xv = jax.ShapeDtypeStruct((2, t, dv), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((2, t), jnp.float32)
    fwd, = _pallas_eqns(jax.make_jaxpr(
        lambda q, k, v: pk._flash_fwd(
            q, k, v, jnp.zeros((2, t), jnp.float32), True, d ** -0.5, blk,
            blk, window, interpret=True, by_class=True))(x, x, xv).jaxpr)
    bwd, = _pallas_eqns(jax.make_jaxpr(
        lambda q, k, v, o, lse, do: pk._flash_bwd_fused(
            q, k, v, None, None, o, lse, do, True, d ** -0.5, blk, blk,
            window, True))(x, x, xv, xv, row, xv).jaxpr)
    budget = 9 if 0 < window < t else 5
    assert fwd_copies <= budget and bwd_copies <= budget
    assert _dots(fwd.params["jaxpr"]) == 2 * fwd_copies
    assert _dots(bwd.params["jaxpr"]) == 5 * bwd_copies
    said = pk.tile_class_stats(t, d, blk, blk, window)
    assert (said["fwd_bodies"], said["bwd_bodies"]) == (fwd_copies,
                                                        bwd_copies)


def test_a_traced_offset_and_a_mask_free_call_keep_the_one_masked_body():
    """flash_attention_piece (a traced q offset, or none: the ring's
    diagonal chunk) and a non-causal flash_attention cannot or need not
    know a tile's class: one copy of the tile's computation a kernel, as
    before, in the two-kernel backward too."""
    from paddle_tpu.ops import pallas_kernels as pk

    t, d, blk = 1024, 64, 256
    x = jax.ShapeDtypeStruct((2, t, d), jnp.float32)
    qoff = jax.ShapeDtypeStruct((1,), jnp.int32)

    def piece(q, k, v, qoff):
        o, lse = pk.flash_attention_piece(q, k, v, True, None, blk, blk, 0,
                                          qoff)
        return jnp.sum(o) + jnp.sum(lse)

    def plain(q, k, v, qoff):
        return jnp.sum(pk.flash_attention(q, k, v, None, False, None, blk,
                                          blk))

    def diagonal_chunk(q, k, v, qoff):
        return jnp.sum(pk.flash_attention_piece(q, k, v, True, None, blk,
                                                blk)[0])

    for fn, dots in ((piece, [2, 3, 4]), (diagonal_chunk, [2, 3, 4]),
                     (plain, [2, 5])):
        kernels = _pallas_eqns(jax.make_jaxpr(jax.grad(
            fn, argnums=(0, 1, 2)))(x, x, x, qoff).jaxpr)
        assert [_dots(e.params["jaxpr"]) for e in kernels] == dots


def test_tile_class_stats_at_the_cells_shapes():
    """What the lowering records for the benchmark: the visible pairs
    (causal T (T + 1) / 2, a band its own count), the pairs the bodies
    compute (a whole or masked tile all of its pairs, a tile in four strips
    10 / 16) and the tiles by class."""
    from paddle_tpu.ops import pallas_kernels as pk

    said = pk.tile_class_stats(8192, 128, 1024, 1024, 2048)
    assert said["tiles"] == {"whole": 7, "diag": 8, "edge": 6, "both": 0}
    assert said["visible"] == 14681088  # 2048 x 8192 - 2048 x 2047 / 2
    assert said["bwd_pairs"] == 1024 * 1024 * (7 + 14 * 10 // 16) \
        + 1024 * 1024 * 12 // 16
    said = pk.tile_class_stats(8192, 128, 1024, 1024, 0)
    assert said["tiles"] == {"whole": 28, "diag": 8, "edge": 0, "both": 0}
    assert said["visible"] == 8192 * 8193 // 2
    assert said["bwd_pairs"] == 1024 * 1024 * 33
    assert said["fwd_pairs"] == 1024 * 1024 * 34  # two strips: 3 / 4
    said = pk.tile_class_stats(1024, 64, 1024, 1024, 0)
    assert said["tiles"] == {"whole": 0, "diag": 1, "edge": 0, "both": 0}
    assert said["fwd_pairs"] == 1024 * 1024  # one tile a head: whole
    assert said["bwd_pairs"] / said["visible"] == pytest.approx(1.2488,
                                                                abs=1e-4)
    # past the one kernel's dq scratch: two masked kernels a tile
    said = pk.tile_class_stats(16384, 128, 1024, 1024, 0)
    assert said["bwd_bodies"] == 1
    assert said["bwd_pairs"] == 2 * 1024 * 1024 * 136
    # a window narrower than a block cuts every tile it reaches twice
    said = pk.tile_class_stats(8192, 128, 1024, 1024, 1000)
    assert said["tiles"] == {"whole": 0, "diag": 0, "edge": 7, "both": 8}
    assert said["fwd_pairs"] == said["bwd_pairs"] == 1024 * 1024 * 15


def test_fused_attention_layer_window():
    """The window attr flows through the op and layer (dense path here;
    the pallas path shares the masks by the kernel test above)."""
    from paddle_tpu import layers

    rng = np.random.RandomState(12)
    xv = rng.rand(2, 2, 16, 8).astype("float32")
    q = layers.data("qw", shape=[2, 16, 8])
    att = layers.fused_attention(q, q, q, causal=True, window=4)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    (out,) = exe.run(feed={"qw": xv}, fetch_list=[att])
    qf = jnp.asarray(xv.reshape(4, 16, 8))
    ref = _dense_attention(qf, qf, qf, True, 1.0 / np.sqrt(8), window=4)
    np.testing.assert_allclose(np.asarray(out).reshape(4, 16, 8),
                               np.asarray(ref), rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError, match="window requires causal"):
        layers.fused_attention(q, q, q, causal=False, window=4)


def test_flash_attention_piece_qoff_matches_global_band():
    """The traced q-position offset (SMEM scalar): a chunk pair with
    global offset D behaves exactly like the corresponding rows of a
    global causal/windowed attention — values and q/k/v grads.  (The
    ring's off-diagonal chunks will ride this on-chip; under shard_map
    interpret mode the varying-SMEM operand trips jax's vma typing, so
    the ring currently uses the dense band off-diagonal on CPU.)"""
    from paddle_tpu.ops.pallas_kernels import flash_attention_piece

    rng = np.random.RandomState(13)
    bh, t, d, W = 2, 16, 8, 12
    # global sequence of 2 chunks: q is chunk 1, k/v are chunk 0
    q = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    k = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    v = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    scale = 1.0 / np.sqrt(d)
    qoff = jnp.asarray([t], jnp.int32)  # q global base = t, k base = 0

    def ref(q, k, v):
        s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
        qp = t + np.arange(t)[:, None]
        kp = np.arange(t)[None, :]
        mask = (qp >= kp) & (qp - kp < W)
        m = jnp.max(jnp.where(jnp.asarray(mask), s, -1e30), -1)
        p = jnp.exp(jnp.where(jnp.asarray(mask), s, -1e30) - m[..., None])
        l = jnp.sum(p, -1)
        return (jnp.einsum("bqk,bkd->bqd", p, v) / l[..., None],
                m + jnp.log(l))

    o, lse = flash_attention_piece(q, k, v, True, scale, 8, 8, W, qoff)
    o_ref, lse_ref = ref(q, k, v)
    # rows with NO in-window key are undefined garbage by contract (the
    # ring merge washes them out via lse ~ -1e30) — compare defined rows
    qp = t + np.arange(t)
    valid = (qp[:, None] >= np.arange(t)[None, :])         & (qp[:, None] - np.arange(t)[None, :] < W)
    rows = valid.any(axis=1)
    np.testing.assert_allclose(np.asarray(o)[:, rows],
                               np.asarray(o_ref)[:, rows],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse)[:, rows],
                               np.asarray(lse_ref)[:, rows],
                               rtol=2e-4, atol=2e-4)
    # undefined rows still wash out of a merge: lse must be tiny
    assert (np.asarray(lse)[:, ~rows] < -1e29).all()

    mask_rows = jnp.asarray(rows)

    gf = jax.grad(lambda q, k, v: jnp.sum(jnp.where(
        mask_rows[None, :, None], flash_attention_piece(
            q, k, v, True, scale, 8, 8, W, qoff)[0], 0.0) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(jnp.where(
        mask_rows[None, :, None], ref(q, k, v)[0], 0.0) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_flash_qoff_undefined_rows_zero_grads():
    """Rows with no visible key (possible under qoff+window) contribute
    ZERO gradients even when the loss touches them — the backward guards
    p by the row's lse sentinel instead of trusting callers to mask do."""
    from paddle_tpu.ops.pallas_kernels import flash_attention_piece

    rng = np.random.RandomState(14)
    bh, t, d, W = 1, 16, 8, 12
    q = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    k = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    v = jnp.asarray(rng.randn(bh, t, d).astype("float32"))
    qoff = jnp.asarray([t], jnp.int32)
    g = jax.grad(lambda q, k, v: jnp.sum(flash_attention_piece(
        q, k, v, True, 1 / np.sqrt(d), 8, 8, W, qoff)[0]),
        argnums=(0, 1, 2))(q, k, v)
    for a in g:
        assert np.isfinite(np.asarray(a)).all()
    # q global rows 27..31 see no key within the window -> zero dq
    assert np.abs(np.asarray(g[0])[0, 11:]).max() == 0.0


# ---------------------------------------------------------------------------
# matmul-epilogue kernels (PR 11 primitive-kernel layer)
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# the vocabulary head (math_ops.linear_xent_tiled: plain XLA ops, no kernel)
# ---------------------------------------------------------------------------
def _tiled_case(shape, eps, transpose_w, dtype, seed=28, vocab=33):
    """x, w, labels (two of them out of range), a non-uniform dy, and the
    _linear_xent_dense twin of linear_xent_tiled on them."""
    from paddle_tpu.ops.math_ops import _linear_xent_dense

    rng = np.random.RandomState(seed)
    h = shape[-1]
    x = jnp.asarray(rng.randn(*shape), dtype)
    w = jnp.asarray(
        rng.randn(*((vocab, h) if transpose_w else (h, vocab))) * 0.3, dtype)
    lbl = rng.randint(0, vocab, shape[:-1])
    lbl.flat[1], lbl.flat[2] = -1, vocab + 3
    lbl = jnp.asarray(lbl, jnp.int32)
    dy = jnp.asarray(rng.rand(*shape[:-1], 1) + 0.5, jnp.float32)

    def dense(x, w):
        return _linear_xent_dense(
            x.reshape(-1, h), w.T if transpose_w else w, lbl.reshape(-1),
            eps).reshape(shape[:-1] + (1,))

    return x, w, lbl, dy, dense


@pytest.fixture
def four_row_tiles(monkeypatch):
    """The head's tile budget cut to 8 rows of a 33-wide vocabulary, so
    that toy shapes take several tiles."""
    from paddle_tpu.ops import math_ops

    monkeypatch.setattr(math_ops, "_LXENT_TILE_BYTES", 4 * 33 * 8)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("transpose_w", [False, True])
@pytest.mark.parametrize("eps", [0.0, 0.1])
@pytest.mark.parametrize("shape,one_tile", [
    ((32, 16), False),      # [R, H]: four tiles of 8 rows
    ((30, 16), False),      # rows not divisible by the tile: padded tail
    ((3, 20, 16), False),   # [B, T, H]: the time axis is the scanned one
    ((3, 21, 16), False),   # ... and does not divide: every row pads
    ((3, 20, 16), True),    # what a GSPMD-partitioned program gets
])
def test_linear_xent_tiled_matches_dense_autodiff(
        four_row_tiles, shape, one_tile, eps, transpose_w, dtype, tol):
    """Loss and both gradients of the default head against jax's autodiff
    of _linear_xent_dense, under a non-uniform dy, with out-of-range
    labels among the rows."""
    from paddle_tpu.ops.math_ops import linear_xent_tiled

    x, w, lbl, dy, dense = _tiled_case(shape, eps, transpose_w, dtype)
    loss, vjp = jax.vjp(
        lambda x, w: linear_xent_tiled(x, w, lbl, eps, transpose_w,
                                       one_tile), x, w)
    ref, ref_vjp = jax.vjp(dense, x, w)
    assert loss.dtype == jnp.float32 and loss.shape == shape[:-1] + (1,)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               rtol=tol, atol=tol)
    for got, want in zip(vjp(dy), ref_vjp(dy)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=10 * tol, atol=tol)
    # the label convention: a label below 0 or past the vocabulary
    # contributes the smoothing term only
    h = shape[-1]
    z = np.asarray(x, np.float32).reshape(-1, h)[1:3] @ np.asarray(
        w.T if transpose_w else w, np.float32)
    lse = np.log(np.exp(z).sum(-1))
    np.testing.assert_allclose(np.asarray(loss).reshape(-1)[1:3],
                               eps * (lse - z.mean(-1)), rtol=tol, atol=tol)
    if eps == 0.0:
        # ... which without smoothing is nothing, in both gradients: no
        # dx on those rows, and a dw that does not see their dy
        dx, dw = vjp(dy)
        assert not np.asarray(loss).reshape(-1)[1:3].any()
        assert not np.asarray(dx, np.float32).reshape(-1, h)[1:3].any()
        dy_in = dy.reshape(-1).at[1:3].set(0.0).reshape(dy.shape)
        np.testing.assert_array_equal(np.asarray(dw, np.float32),
                                      np.asarray(vjp(dy_in)[1], np.float32))


@pytest.mark.parametrize("batch,length,vocab,steps", [
    (1, 4096, 50257, 1024),   # GPT-2's head as [R, H]
    (4, 1024, 50257, 256),    # ... and as [B, T, H]: 1024 rows a tile
    (128, 256, 10000, 32),    # Transformer-base: 4096 rows a tile
    (1, 32768, 10000, 4096),
    (1, 64, 300, 64),         # fits whole: one tile
    (1, 1361, 50257, 1328),   # a prime length: padded last tile
    (512, 7, 50257, 2),       # fewer than 8 steps fit: no alignment
])
def test_lxent_tile_len_comes_from_the_shapes(batch, length, vocab, steps):
    from paddle_tpu.ops.math_ops import _LXENT_TILE_BYTES, _lxent_tile_len

    got = _lxent_tile_len(batch, length, vocab)
    assert got == steps
    assert got == length or 4 * batch * got * vocab <= _LXENT_TILE_BYTES


@pytest.mark.parametrize("transpose_w", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_linear_xent_tiled_forms_the_logits_gradient_once(
        four_row_tiles, dtype, transpose_w):
    """The mechanism itself, read from the jaxpr of loss-and-gradients at
    a shape with four tiles: no array of R x V elements exists (the
    dense reference's jaxpr, walked the same way, has one), the
    [tile, V] logits gradient is ONE array in the operands' dtype feeding
    both gradient dots, three dots in the backward's loop body and one in
    the forward's, and the trace counted one engagement and no pallas
    hit."""
    import jax.extend.core as jcore

    from paddle_tpu.ops import kernel_tuning as kt
    from paddle_tpu.ops.math_ops import linear_xent_tiled

    R, H, V = 32, 16, 33
    x, w, lbl, dy, dense = _tiled_case((R, H), 0.1, transpose_w, dtype)

    def loss_and_grads(x, w, head=lambda x, w: linear_xent_tiled(
            x, w, lbl, 0.1, transpose_w)):
        loss, vjp = jax.vjp(head, x, w)
        return loss, vjp(dy)

    kt.reset_attribution()
    jaxpr = jax.make_jaxpr(loss_and_grads)(x, w).jaxpr
    att = kt.attribution()
    assert att["dense_vjp_hits"] == {"xent": 1} and att["pallas_hits"] == {}

    def walk(jaxpr, depth, out):
        for eqn in jaxpr.eqns:
            out.append((eqn, depth))
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (list, tuple))
                            else [val]):
                    if isinstance(sub, jcore.ClosedJaxpr):
                        sub = sub.jaxpr
                    if isinstance(sub, jcore.Jaxpr):
                        walk(sub, depth + (eqn.primitive.name == "scan"),
                             out)
        return out

    def largest(eqns):
        return max(int(np.prod(v.aval.shape)) for eqn, _ in eqns
                   for v in list(eqn.invars) + list(eqn.outvars)
                   if getattr(getattr(v, "aval", None), "shape", None))

    eqns = walk(jaxpr, 0, [])
    assert largest(eqns) < R * V
    assert largest(walk(jax.make_jaxpr(
        lambda x, w: loss_and_grads(x, w, dense))(x, w).jaxpr, 0, [])) >= R * V
    scans = [eqn for eqn, _ in eqns if eqn.primitive.name == "scan"]
    assert [int(e.params["length"]) for e in scans] == [4, 4]
    dots = [eqn for eqn, depth in eqns
            if eqn.primitive.name == "dot_general" and depth == 1]
    assert len(dots) == 4  # forward 1; backward: recompute, dx, dw
    tile_v = (1, R // 4, V)
    fed = [v for eqn in dots for v in eqn.invars
           if tuple(v.aval.shape) == tile_v]
    assert len(fed) == 2 and fed[0] is fed[1]  # one array, two dots
    assert fed[0].aval.dtype == jnp.dtype(dtype)


@pytest.mark.parametrize("form", ["mul", "tied_matmul", "mul_smoothed"])
def test_fused_linear_xent_op_and_its_grad_match_the_unfused_chain(
        form, monkeypatch):
    """Through the op, default flags: fused_linear_xent and its generic
    _grad op (jax.vjp of the lowering, so linear_xent_tiled's VJP, here
    over three row tiles) train the head's weight AND the layer below it
    exactly as the unfused projection -> xent chain does."""
    from paddle_tpu.ops import math_ops
    from paddle_tpu.transpiler import apply_pass

    B, T, H, V = 2, 6, 8, 20
    monkeypatch.setattr(math_ops, "_LXENT_TILE_BYTES", 4 * V * B * 2)
    rng = np.random.RandomState(39)
    feed = {"x": rng.rand(B, T, H).astype("float32"),
            "lbl": rng.randint(0, V, (B, T, 1)).astype("int64")}

    def run(fuse):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.framework.program_guard(main, startup):
            startup.random_seed = 12
            x = layers.data("x", shape=[T, H])
            lbl = layers.data("lbl", shape=[T, 1], dtype="int64")
            hid = layers.fc(x, H, num_flatten_dims=2, act="tanh",
                            param_attr=fluid.ParamAttr(name="below_w"))
            if form == "tied_matmul":
                table = layers.create_parameter(
                    shape=[V, H], dtype="float32", name="head_w")
                logits = layers.matmul(hid, table, transpose_y=True)
            else:
                logits = layers.fc(
                    hid, V, num_flatten_dims=2, bias_attr=False,
                    param_attr=fluid.ParamAttr(name="head_w"))
            if form == "mul_smoothed":
                soft = layers.label_smooth(
                    layers.one_hot(lbl, V), epsilon=0.1)
                cost = layers.softmax_with_cross_entropy(
                    logits, soft, soft_label=True)
            else:
                cost = layers.softmax_with_cross_entropy(logits, lbl)
            loss = layers.reduce_mean(cost)
            if fuse:
                apply_pass(main, "smooth_label_xent_fuse_pass")
                apply_pass(main, "linear_xent_fuse_pass")
                assert main._linear_xent_fused_count == 1
            fluid.optimizer.SGD(0.5).minimize(loss)
        types = [op.type for op in main.global_block().ops]
        assert ("fused_linear_xent_grad" in types) == fuse
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            losses = [float(np.asarray(exe.run(
                main, feed=feed, fetch_list=[loss])[0])) for _ in range(3)]
            return losses, [np.array(scope.get(n))
                            for n in ("head_w", "below_w")]

    (l0, w0), (l1, w1) = run(False), run(True)
    np.testing.assert_allclose(l0, l1, rtol=1e-5, atol=1e-6)
    for a, b in zip(w0, w1):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mask", ["none", "kbias", "causal", "causal_kbias"])
@pytest.mark.parametrize("t,d", [(64, 64), (256, 64), (128, 128)])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_short_attention_matches_dense(dtype, tol, t, d, mask):
    """The one-tile form (interpreted) against `_dense_attention`: forward,
    and dq / dk / dv / dbias under `jax.grad`, in float32 and bfloat16, at
    the two Transformer-base cells' head shapes (T = 64 packs two heads
    into a tile's lanes) and at 128-wide heads.  The key bias pads each
    row's tail and row 1 whole: a fully padded row comes out finite and as
    dense gives it, forward and backward (the backward rebuilds its
    probabilities from the saved max and 1 / sum, which float32 holds
    beside a bias of -1e9 where their logsumexp would lose log n)."""
    rng = np.random.RandomState(t + d)
    bh = 4
    q, k, v = (jnp.asarray(rng.randn(bh, t, d), dtype) for _ in range(3))
    causal = mask.startswith("causal")
    kbias = None
    if mask.endswith("kbias"):
        kb = np.where(np.arange(t)[None, :] < t - 5, 0.0, -1e9) + rng.randn(
            bh, t)
        kb[1] = -1e9
        kbias = jnp.asarray(kb, jnp.float32)
    scale = 1.0 / np.sqrt(d)
    w = jnp.cos(jnp.arange(bh * t * d, dtype=jnp.float32)).reshape(bh, t, d)
    argnums = (0, 1, 2, 3) if kbias is not None else (0, 1, 2)

    def close(got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        assert np.isfinite(got).all()
        assert (np.max(np.abs(got - want))
                <= tol * max(1.0, np.max(np.abs(want))))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

    one_tile = lambda q, k, v, kb: short_attention(  # noqa: E731
        q, k, v, kb, causal, scale)
    dense = lambda q, k, v, kb: _dense_attention(  # noqa: E731
        q, k, v, causal, scale, kb)
    out = one_tile(q, k, v, kbias)
    assert out.shape == (bh, t, d) and out.dtype == q.dtype
    close(out, dense(q, k, v, kbias))
    got = jax.grad(loss(one_tile), argnums)(q, k, v, kbias)
    want = jax.grad(loss(dense), argnums)(q, k, v, kbias)
    for g, r, like in zip(got, want, (q, k, v, kbias)):
        assert g.shape == like.shape and g.dtype == like.dtype
        close(g, r)
        if kbias is not None:  # the fully padded row against its own scale
            close(g[1], r[1])


def test_short_attention_plan_is_a_function_of_the_shapes():
    """Heads a grid step and heads a tile from (B*H, T, d, dtype) alone:
    the two cells' plans, a width of 128, float32's, and a B*H the pack
    does not divide."""
    from paddle_tpu.ops import pallas_kernels as pk

    assert pk._short_plan(1024, 256, 64, 64, 2) == pk._ShortPlan(1, 8)
    assert pk._short_plan(4096, 64, 64, 64, 2) == pk._ShortPlan(2, 32)
    assert pk._short_plan(512, 256, 128, 128, 2) == pk._ShortPlan(1, 8)
    for args in ((1024, 256, 64, 64, 2), (96, 384, 64, 64, 4),
                 (7, 64, 64, 64, 2)):
        plan = pk._short_plan(*args)
        assert plan == pk._short_plan(*args)
        assert args[0] % (plan.pack * plan.heads) == 0
    assert pk._short_plan(7, 64, 64, 64, 2).pack == 1


def _heads_first(x, h):
    b, t, _ = x.shape
    return x.reshape(b, t, h, -1).transpose(0, 2, 1, 3).reshape(b * h, t, -1)


@pytest.mark.parametrize("mask", ["none", "kbias", "causal", "causal_kbias"])
@pytest.mark.parametrize("t,h,d", [(64, 8, 64), (256, 8, 64), (64, 2, 64)])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_short_attention_in_place_matches_dense(dtype, tol, t, h, d, mask):
    """The one-tile form reading the projections' [B, T, H d] in place
    (`heads=H`, interpreted) against `_dense_attention` on the transposed
    operands: forward, and dq / dk / dv / dbias under `jax.grad`, in float32
    and bfloat16, at the two Transformer-base cells' attention shapes (eight
    heads of 64, two to a product) and at one pair of heads alone.  The
    batch of 3 is odd: a grid step holds one sequence or all three, never
    the largest count the VMEM budget allows.  The key bias [B, T] pads each
    row's tail and row 1 whole: a fully padded row comes out finite and as
    dense gives it, forward and backward."""
    rng = np.random.RandomState(t + d + h)
    b = 3
    q, k, v = (jnp.asarray(rng.randn(b, t, h * d), dtype) for _ in range(3))
    causal = mask.startswith("causal")
    kbias = None
    if mask.endswith("kbias"):
        kb = np.where(np.arange(t)[None, :] < t - 5, 0.0, -1e9) + rng.randn(
            b, t)
        kb[1] = -1e9
        kbias = jnp.asarray(kb, jnp.float32)
    scale = 1.0 / np.sqrt(d)
    w = jnp.cos(jnp.arange(b * t * h * d, dtype=jnp.float32)).reshape(
        b, t, h * d)
    argnums = (0, 1, 2, 3) if kbias is not None else (0, 1, 2)

    def close(got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        assert np.isfinite(got).all()
        assert (np.max(np.abs(got - want))
                <= tol * max(1.0, np.max(np.abs(want))))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

    def dense(q, k, v, kb):
        rows = None if kb is None else jnp.broadcast_to(
            kb[:, None], (b, h, t)).reshape(b * h, t)
        o = _dense_attention(*(_heads_first(x, h) for x in (q, k, v)),
                             causal, scale, rows)
        return o.reshape(b, h, t, d).transpose(0, 2, 1, 3).reshape(
            b, t, h * d)

    in_place = lambda q, k, v, kb: short_attention(  # noqa: E731
        q, k, v, kb, causal, scale, h)
    out = in_place(q, k, v, kbias)
    assert out.shape == (b, t, h * d) and out.dtype == q.dtype
    close(out, dense(q, k, v, kbias))
    got = jax.grad(loss(in_place), argnums)(q, k, v, kbias)
    want = jax.grad(loss(dense), argnums)(q, k, v, kbias)
    for g, r, like in zip(got, want, (q, k, v, kbias)):
        assert g.shape == like.shape and g.dtype == like.dtype
        close(g, r)
        if kbias is not None:  # the fully padded row against its own scale
            close(g[1], r[1])


@pytest.mark.parametrize("h,d,dv,group", [(3, 64, 64, 1), (2, 128, 128, 1),
                                          (4, 32, 32, 4), (2, 64, 32, 2)])
def test_short_attention_in_place_groups_heads_by_what_fills_the_lanes(
        h, d, dv, group):
    """Heads a product: as many as fill 128 lanes and divide H (an odd H of
    64-wide heads goes one by one, a 64-lane slice each; 128-wide heads one
    a tile; four heads of 32), V of another width than Q and K; each against
    dense, forward and dq / dk / dv, causal under a key bias."""
    from paddle_tpu.ops import pallas_kernels as pk

    b, t = 2, 64
    assert pk._inplace_plan(b, t, h, d, dv, 4).group == group
    rng = np.random.RandomState(h + d)
    q, k = (jnp.asarray(rng.randn(b, t, h * d), "float32") for _ in range(2))
    v = jnp.asarray(rng.randn(b, t, h * dv), "float32")
    kb = jnp.asarray(np.where(np.arange(t)[None, :] < t - 9, 0.0, -1e9)
                     + rng.randn(b, t), jnp.float32)
    scale = d ** -0.5
    w = jnp.cos(jnp.arange(b * t * h * dv, dtype=jnp.float32)).reshape(
        b, t, h * dv)

    def dense(q, k, v):
        rows = jnp.broadcast_to(kb[:, None], (b, h, t)).reshape(b * h, t)
        o = _dense_attention(*(_heads_first(x, h) for x in (q, k, v)), True,
                             scale, rows)
        return o.reshape(b, h, t, dv).transpose(0, 2, 1, 3).reshape(
            b, t, h * dv)

    in_place = lambda q, k, v: short_attention(  # noqa: E731
        q, k, v, kb, True, scale, h)
    got = jax.value_and_grad(lambda *a: jnp.sum(in_place(*a) * w),
                             (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda *a: jnp.sum(dense(*a) * w),
                              (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(g, r, atol=2e-5 * max(
            1.0, float(jnp.max(jnp.abs(r)))))


def test_short_attention_in_place_plan_is_a_function_of_the_shapes():
    """Sequences a grid step, heads a product and sequences a loop body from
    (B, T, H, d, dv, dtype) alone: the two cells' plans, float32's, 128-wide
    heads, a batch the largest count does not divide (the budget allows 21
    here: 512 takes 16, 510 = 2 x 3 x 5 x 17 takes 17, a prime 509 one) and
    more groups of heads than a body's 32 chains."""
    from paddle_tpu.ops import pallas_kernels as pk

    assert pk._inplace_plan(512, 64, 8, 64, 64, 2) == pk._InPlacePlan(
        16, 2, 8)
    assert pk._inplace_plan(128, 256, 8, 64, 64, 2) == pk._InPlacePlan(
        4, 2, 4)
    assert pk._inplace_plan(512, 64, 8, 64, 64, 4) == pk._InPlacePlan(8, 2, 8)
    assert pk._inplace_plan(512, 64, 4, 128, 128, 2) == pk._InPlacePlan(
        16, 1, 8)
    assert pk._inplace_plan(510, 64, 8, 64, 64, 2) == pk._InPlacePlan(
        17, 2, 1)
    assert pk._inplace_plan(7, 64, 8, 64, 64, 2) == pk._InPlacePlan(7, 2, 7)
    assert pk._inplace_plan(2, 64, 72, 64, 64, 2) == pk._InPlacePlan(2, 2, 1)
    for args in ((512, 64, 8, 64, 64, 2), (96, 256, 6, 64, 64, 4),
                 (509, 64, 3, 64, 64, 2)):
        plan = pk._inplace_plan(*args)
        assert plan == pk._inplace_plan(*args)
        assert args[0] % plan.seqs == 0 and args[2] % plan.group == 0
        assert plan.seqs % plan.unroll == 0
        assert plan.unroll * args[2] // plan.group <= 32
    assert pk._inplace_plan(509, 64, 3, 64, 64, 2) == pk._InPlacePlan(1, 1, 1)


def test_every_kernel_lowers_for_tpu_without_a_chip(monkeypatch):
    """Cross-lower each chip_smoke kernel case (the repo's model shapes,
    forward and backward) for the TPU platform on this host.  That runs
    the Pallas -> Mosaic lowering rule and its block-spec legality checks,
    which interpret mode skips: a (1, 1)-blocked SMEM spec once passed
    every interpreted test and was refused at this stage on first contact
    with the chip.  What Mosaic itself does with the lowered module still
    needs the chip (chip_smoke.py)."""
    import chip_smoke
    from paddle_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    cases = chip_smoke.kernel_cases(rehearse=False)
    assert sorted(cases) == sorted(pk.__all__)
    for name, (kernel, _dense, make_args, _where) in cases.items():
        lowered = jax.jit(kernel).trace(
            *jax.eval_shape(make_args)).lower(lowering_platforms=("tpu",))
        assert "tpu_custom_call" in lowered.as_text(), name


@pytest.mark.parametrize("held, router", [(8, "softmax"), (2, "sigmoid")],
                         ids=["all_experts", "a_chips_share"])
def test_grouped_matmul_lowers_for_tpu_without_a_chip(held, router):
    """moe_ffn's grouped matmuls are megablox's Pallas kernels on a step
    placed on the chip (ops/moe_ops.grouped_matmul chooses by the placed
    platform, LowerCtx.platform, and shape): cross-lower the op's forward
    and backward at a tile-aligned size for the TPU platform on this host,
    as the test above does for the repo's own kernels.  Six Mosaic calls:
    two matmuls forward, and for each the rows' gradient (gmm over rhs^T)
    and the weights' (tgmm); the same six when the op holds 2 of the 8
    experts its sigmoid router chooses among."""
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.ops import kernel_tuning as kt

    on_chip = LowerCtx(platform="tpu")
    n, d, f, e, k = 256, 256, 128, 8, 2
    assert moe_ops._megablox_fits(
        on_chip, jax.ShapeDtypeStruct((n * k, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((held, d, 2 * f), jnp.bfloat16))
    assert not moe_ops._megablox_fits(
        on_chip, jax.ShapeDtypeStruct((n * k - 8, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((held, d, 2 * f), jnp.bfloat16))
    # the same step placed on this host keeps ragged_dot
    assert not moe_ops._megablox_fits(
        LowerCtx(platform="cpu"),
        jax.ShapeDtypeStruct((n * k, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((held, d, 2 * f), jnp.bfloat16))

    def loss(x, wr, wgu, wd):
        out = moe_ops._moe_ffn(
            on_chip, {"X": [x], "RouterW": [wr], "GateUpW": [wgu],
                      "DownW": [wd]},
            {"top_k": k, "router": router, "expert_offset": e - held})
        return out["Y"][0].astype(jnp.float32).sum() + out["AuxLoss"][0].sum()

    before = kt.attribution()["pallas_hits"].get("grouped_matmul", 0)
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).trace(
        jax.ShapeDtypeStruct((n, d), jnp.float32),
        jax.ShapeDtypeStruct((d, e), jnp.float32),
        jax.ShapeDtypeStruct((held, d, 2 * f), jnp.bfloat16),
        jax.ShapeDtypeStruct((held, f, d), jnp.bfloat16),
    ).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") == 6
    assert kt.attribution()["pallas_hits"]["grouped_matmul"] == before + 2
