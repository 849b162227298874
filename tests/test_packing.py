"""Sequence packing (reader/packing.py) + segment-masked attention
(`fused_attention(segment_ids=...)`): packed rows must behave exactly
like the original unpacked sequences — no cross-sequence leakage."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.reader import pack_sequences


def test_pack_sequences_structure():
    rng = np.random.RandomState(0)
    seqs = [rng.randint(1, 100, (n,)) for n in (7, 3, 8, 2, 6, 5)]
    tokens, seg, pos = pack_sequences(seqs, seq_len=10)
    # every sequence appears intact in exactly one row, contiguous
    found = 0
    for s in seqs:
        hits = 0
        for r in range(tokens.shape[0]):
            for off in range(0, 10 - s.size + 1):
                if (tokens[r, off:off + s.size] == s).all() and \
                        len(set(seg[r, off:off + s.size])) == 1 and \
                        seg[r, off] > 0 and \
                        (pos[r, off:off + s.size] == np.arange(s.size)).all():
                    hits += 1
                    break
        found += hits
    assert found == len(seqs)
    # padding is segment 0, fill rate beats one-row-per-sequence
    total = sum(s.size for s in seqs)
    assert (seg > 0).sum() == total
    assert tokens.shape[0] < len(seqs)
    # a too-long sequence raises
    with pytest.raises(ValueError, match="exceeds seq_len"):
        pack_sequences([np.arange(11)], seq_len=10)


def test_segment_masked_attention_matches_unpacked():
    """Two sequences packed into one row with causal self-attention ==
    each sequence attended alone: positions of seq A in the packed
    output must equal A's standalone attention output."""
    rng = np.random.RandomState(1)
    h, d = 2, 8
    la, lb, L = 5, 3, 8

    def run(qkv, seg=None, t=None):
        t = t or qkv[0].shape[2]
        main, startup = fluid.Program(), fluid.Program()
        with fluid.framework.program_guard(main, startup):
            q = layers.data("q", shape=[h, t, d])
            k = layers.data("k", shape=[h, t, d])
            v = layers.data("v", shape=[h, t, d])
            kwargs = {}
            feed = {"q": qkv[0], "k": qkv[1], "v": qkv[2]}
            if seg is not None:
                sv = layers.data("seg", shape=[t], dtype="int32")
                kwargs["segment_ids"] = sv
                feed["seg"] = seg
            out = layers.fused_attention(q, k, v, causal=True, **kwargs)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            (o,) = exe.run(main, feed=feed, fetch_list=[out])
        return np.asarray(o)

    a = rng.rand(1, h, la, d).astype("float32")
    b = rng.rand(1, h, lb, d).astype("float32")
    packed = np.zeros((1, h, L, d), "float32")
    packed[:, :, :la] = a
    packed[:, :, la:la + lb] = b
    seg = np.zeros((1, L), "int32")
    seg[0, :la] = 1
    seg[0, la:la + lb] = 2

    got = run((packed,) * 3, seg=seg)
    ref_a = run((a,) * 3)
    ref_b = run((b,) * 3)
    np.testing.assert_allclose(got[:, :, :la], ref_a, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[:, :, la:la + lb], ref_b,
                               rtol=1e-5, atol=1e-6)


def test_segment_attention_grads_flow():
    """minimize() through segment-masked attention works (int ids get no
    grad; q/k/v do) and the loss is finite."""
    rng = np.random.RandomState(2)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x = layers.data("x", shape=[2, 8, 8])
        seg = layers.data("seg", shape=[8], dtype="int32")
        q = layers.fc(x, 8, num_flatten_dims=3)
        out = layers.fused_attention(q, q, q, causal=True, segment_ids=seg)
        loss = layers.mean(out * out)
        fluid.optimizer.SGD(0.1).minimize(loss)
    sv = np.zeros((2, 8), "int32")
    sv[:, :5] = 1
    sv[:, 5:] = 2
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        (lv,) = exe.run(main, feed={
            "x": rng.rand(2, 2, 8, 8).astype("float32"), "seg": sv},
            fetch_list=[loss])
    assert np.isfinite(np.asarray(lv)).all()


@pytest.mark.slow  # full-train/full-model integration pass (tens of seconds on this 2-core sandbox); rides scripts/ci.sh --full — the fast lane must finish inside tier-1's time budget
def test_flash_segment_ids_match_dense():
    """Flash kernels with segment ids (interpret mode) == dense-XLA
    segment masking: forward and all grads, causal and bidirectional,
    at both single-block and multi-block sizes."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import (
        _dense_attention, flash_attention)

    rng = np.random.RandomState(4)
    for T, bq, bk in ((16, 16, 16), (256, 128, 128)):
        BH, d = 2, 8
        q, k, v = (jnp.asarray(rng.rand(BH, T, d).astype("float32"))
                   for _ in range(3))
        seg = np.ones((BH, T), np.int32)
        seg[:, T // 3:] = 2
        seg[:, 2 * T // 3:] = 3
        seg = jnp.asarray(seg)
        for causal in (False, True):
            def f_flash(q, k, v):
                o = flash_attention(q, k, v, None, causal, None,
                                    bq, bk, 0, seg)
                return o, jnp.sum(o * o)

            def f_dense(q, k, v):
                o = _dense_attention(q, k, v, causal, 1.0 / d ** 0.5,
                                     seg=seg)
                return o, jnp.sum(o * o)

            of, _ = f_flash(q, k, v)
            od, _ = f_dense(q, k, v)
            np.testing.assert_allclose(np.asarray(of), np.asarray(od),
                                       rtol=2e-5, atol=2e-6)
            gf = jax.grad(lambda *a: f_flash(*a)[1], argnums=(0, 1, 2))(
                q, k, v)
            gd = jax.grad(lambda *a: f_dense(*a)[1], argnums=(0, 1, 2))(
                q, k, v)
            for a, b in zip(gf, gd):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-4, atol=2e-5)


def test_op_segment_ids_ride_the_flash_kernel_where_it_engages(monkeypatch):
    """Where platform and shape engage the blockwise kernel (said here by
    the test: the training path reads no flag), the fused_attention op
    with SegmentIds routes through it (interpret mode on CPU) and matches
    the dense path bit-for-tolerance."""
    from paddle_tpu.ops import nn_ops

    rng = np.random.RandomState(5)
    h, t, d = 2, 16, 8
    qv = rng.rand(2, h, t, d).astype("float32")
    sv = np.ones((2, t), np.int32)
    sv[:, t // 2:] = 2

    def run():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.framework.program_guard(main, startup):
            q = layers.data("q", shape=[h, t, d])
            seg = layers.data("seg", shape=[t], dtype="int32")
            out = layers.fused_attention(q, q, q, causal=True,
                                         segment_ids=seg)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            (o,) = exe.run(main, feed={"q": qv, "seg": sv},
                           fetch_list=[out])
        return np.asarray(o)

    dense = run()
    monkeypatch.setattr(nn_ops, "_flash_engages",
                        lambda ctx, tq, tk, d, dv=None: tq == tk)
    flash = run()
    assert not np.array_equal(flash, dense)  # another lowering ran
    np.testing.assert_allclose(flash, dense, rtol=2e-5, atol=2e-6)
