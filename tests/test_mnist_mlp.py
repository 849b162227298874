"""End-to-end slice: MLP + conv-net training on synthetic MNIST-shaped data.

Mirrors the reference's book test contract (tests/book/test_recognize_digits):
build program -> startup -> train steps -> loss decreases -> save/load ->
infer.
"""

import numpy as np
import pytest

import paddle_tpu as fluid


def _synthetic_batch(bs=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(bs, 784).astype("float32")
    # learnable mapping: label depends on mean of pixel blocks
    y = (x[:, :10].sum(axis=1) * 10 % 10).astype("int64").reshape(bs, 1)
    return x, y


def test_mlp_train_loss_decreases():
    img = fluid.layers.data("img", shape=[784])
    label = fluid.layers.data("label", shape=[1], dtype="int64")
    hidden = fluid.layers.fc(img, size=64, act="relu")
    pred = fluid.layers.fc(hidden, size=10, act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
    acc = fluid.layers.accuracy(pred, label)
    # lr 0.5 overshoots on this near-chance-level task (step-2 loss
    # spikes to ~5.8, then the trajectory plateaus at ~0.905x first —
    # deterministically just ABOVE the 0.9 bar); 0.1 descends cleanly
    # to ~0.85x in the same 30 steps
    opt = fluid.optimizer.SGD(learning_rate=0.1)
    opt.minimize(loss)

    place = fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(fluid.default_startup_program())

    losses = []
    for i in range(30):
        x, y = _synthetic_batch(seed=i % 5)
        lv, av = exe.run(feed={"img": x, "label": y}, fetch_list=[loss, acc])
        losses.append(float(lv))
    assert losses[-1] < losses[0] * 0.9, losses
    assert np.isfinite(losses).all()


def test_conv_net_with_batchnorm_and_adam():
    img = fluid.layers.data("img", shape=[1, 28, 28])
    label = fluid.layers.data("label", shape=[1], dtype="int64")
    c1 = fluid.layers.conv2d(img, num_filters=8, filter_size=3, padding=1, act=None)
    b1 = fluid.layers.batch_norm(c1, act="relu")
    p1 = fluid.layers.pool2d(b1, pool_size=2, pool_stride=2)
    pred = fluid.layers.fc(p1, size=10, act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
    fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    losses = []
    for i in range(15):
        x = rng.rand(16, 1, 28, 28).astype("float32")
        y = (x.mean(axis=(1, 2, 3)) * 30 % 10).astype("int64").reshape(16, 1)
        (lv,) = exe.run(feed={"img": x, "label": y}, fetch_list=[loss])
        losses.append(float(lv))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_save_load_inference_roundtrip(tmp_path):
    img = fluid.layers.data("img", shape=[784])
    label = fluid.layers.data("label", shape=[1], dtype="int64")
    pred = fluid.layers.fc(img, size=10, act="softmax")
    loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
    test_program = fluid.default_main_program().clone(for_test=True)
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    x, y = _synthetic_batch(8)
    exe.run(feed={"img": x, "label": y}, fetch_list=[loss])
    (before,) = exe.run(test_program, feed={"img": x}, fetch_list=[pred])

    model_dir = str(tmp_path / "model")
    fluid.save_inference_model(model_dir, ["img"], [pred], exe)

    # fresh scope + program: load and compare
    with fluid.scope_guard(fluid.Scope()):
        infer_prog, feed_names, fetch_vars = fluid.load_inference_model(model_dir, exe)
        (after,) = exe.run(
            infer_prog, feed={feed_names[0]: x}, fetch_list=fetch_vars
        )
    np.testing.assert_allclose(before, after, rtol=1e-5, atol=1e-6)


def test_feed_dtype_kind_mismatch_raises():
    """Float feed into an int64 data slot errors clearly instead of
    silently flooring ids (the DataFeeder enforce contract)."""
    import pytest

    ids = fluid.layers.data("dt_ids", shape=[1], dtype="int64")
    emb = fluid.layers.embedding(ids, size=[10, 4])
    out = fluid.layers.mean(emb)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    with pytest.raises(TypeError, match="dtype"):
        exe.run(feed={"dt_ids": np.random.rand(4, 1).astype("float32")},
                fetch_list=[out])
    # int32 into int64 stays allowed (width-only difference)
    (v,) = exe.run(feed={"dt_ids": np.zeros((4, 1), "int32")}, fetch_list=[out])
    assert np.isfinite(np.asarray(v)).all()


def test_no_hidden_recompile_across_steps():
    """Each (program, signature) must compile its XLA executable exactly
    ONCE.  Regression: startup outputs were uncommitted while train feeds
    were committed, so run 2 flipped every param's committedness and the
    jit cache silently recompiled the whole program."""
    import numpy as np
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[6])
        y = fluid.layers.data("y", shape=[1], dtype="int64")
        pred = fluid.layers.fc(x, 4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, y))
        fluid.optimizer.Momentum(0.1, momentum=0.9).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        xv = np.random.rand(3, 6).astype("float32")
        yv = np.random.randint(0, 4, (3, 1)).astype("int64")
        for _ in range(3):
            exe.run(main, feed={"x": xv, "y": yv}, fetch_list=[loss])
    for compiled in exe._cache._cache.values():
        assert compiled.compiles == 1, (
            "hidden recompile: one ExecutionCache entry compiled %d times"
            % compiled.compiles)


def test_run_loop_matches_sequential_runs():
    """Executor.run_loop(K): ONE compiled lax.scan call == K sequential
    run() calls — identical final weights and identical last-step loss
    (deterministic program), and the loop executable compiles once."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers

    rng = np.random.RandomState(0)
    xv = rng.rand(16, 12).astype("float32")
    yv = rng.randint(0, 3, (16, 1)).astype("int64")

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.framework.program_guard(main, startup):
            startup.random_seed = 7
            main.random_seed = 7
            x = layers.data("rlx", shape=[12])
            y = layers.data("rly", shape=[1], dtype="int64")
            h = layers.fc(x, 16, act="relu",
                          param_attr=fluid.ParamAttr(name="rl_w1"))
            # dropout makes the test ALSO pin exact RNG-stream parity:
            # iteration i of the loop must draw run()'s step-i keys
            h = layers.dropout(h, 0.3)
            p = layers.fc(h, 3, act="softmax",
                          param_attr=fluid.ParamAttr(name="rl_w2"))
            loss = layers.mean(layers.cross_entropy(p, y))
            fluid.optimizer.Momentum(0.1, momentum=0.9).minimize(loss)
        return main, startup, loss

    K = 5
    # sequential reference: 2K steps, capturing the loss at step K and
    # step 2K (the second window is the reference for the REPEATED
    # run_loop call below)
    main, startup, loss = build()
    s1 = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(s1):
        exe.run(startup)
        for _ in range(K):
            (seq_loss,) = exe.run(main, feed={"rlx": xv, "rly": yv},
                                  fetch_list=[loss])
        w_seq = np.array(s1.get("rl_w1"))
        for _ in range(K):
            (seq_loss2,) = exe.run(main, feed={"rlx": xv, "rly": yv},
                                   fetch_list=[loss])
        w_seq2 = np.array(s1.get("rl_w1"))

    # one compiled loop
    main2, startup2, loss2 = build()
    s2 = fluid.Scope()
    exe2 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(s2):
        exe2.run(startup2)
        (loop_loss,) = exe2.run_loop(K, main2,
                                     feed={"rlx": xv, "rly": yv},
                                     fetch_list=[loss2])
        w_loop = np.array(s2.get("rl_w1"))
        # repeat from the updated state: cache hit, state threads on
        (loop_loss2,) = exe2.run_loop(K, main2,
                                      feed={"rlx": xv, "rly": yv},
                                      fetch_list=[loss2])
        w_loop2 = np.array(s2.get("rl_w1"))
        assert len(exe2._loop_cache) == 1
        (_, jitted), = exe2._loop_cache.values()
        assert jitted._cache_size() == 1, jitted._cache_size()

    np.testing.assert_allclose(np.asarray(loop_loss),
                               np.asarray(seq_loss), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(w_loop, w_seq, rtol=1e-5, atol=1e-6)
    # the REPEATED loop continues from the updated state with run()'s
    # step-6..10 RNG keys: exact parity with steps 6..10 of the
    # sequential chain.  (This replaces an older "loss still decreases"
    # proxy that deterministically flaked once the 16-sample memorization
    # task plateaued inside the second window — parity is the contract,
    # monotone descent never was.)
    np.testing.assert_allclose(np.asarray(loop_loss2),
                               np.asarray(seq_loss2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(w_loop2, w_seq2, rtol=1e-5, atol=1e-6)

    # host-boundary ops are rejected
    import pytest

    mainr, startupr = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(mainr, startupr):
        r = layers.py_reader(capacity=2, shapes=[[-1, 4]], dtypes=["float32"])
        xr = layers.read_file(r)
        layers.reduce_sum(xr)
    with pytest.raises(ValueError, match="host-boundary"):
        exe2.run_loop(2, mainr)


def test_run_loop_failure_reports_invalidated_scope():
    """ADVICE r4 (low) + r5: run_loop donates the rw state to the device;
    if the compiled call fails AFTER donation (buffers deleted) the
    executor must raise a CLEAR error naming the invalidated scope state
    (not a later opaque deleted-buffer error) and roll back its RNG step
    counter — detected by inspecting the donated buffers themselves, not
    by classifying the exception type.  A failure that leaves the
    buffers ALIVE (pre-dispatch argument validation, whatever its
    exception class) must surface plainly: the scope is intact."""
    import numpy as np
    import pytest
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x = layers.data("dlx", shape=[4])
        p = layers.fc(x, 2, param_attr=fluid.ParamAttr(name="dl_w"))
        loss = layers.mean(p)
        fluid.optimizer.SGD(0.1).minimize(loss)

    xv = np.random.RandomState(0).rand(8, 4).astype("float32")
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run_loop(2, main, feed={"dlx": xv}, fetch_list=[loss])
        step_before = exe._step

        def boom_donated(feeds, ro_state, rw_state, keys):
            # model a mid-flight device failure: by then the donated rw
            # buffers are already consumed (deleted)
            for v in rw_state.values():
                if hasattr(v, "delete"):
                    v.delete()
            raise TypeError("callback exploded after dispatch")

        real_cache = dict(exe._loop_cache)
        exe._loop_cache = {
            k: (traced, boom_donated) for k, (traced, _)
            in real_cache.items()
        }
        # a TypeError AFTER donation still gets the clear diagnostic
        with pytest.raises(RuntimeError, match="scope state .* invalidated"
                           "|state was donated"):
            exe.run_loop(2, main, feed={"dlx": xv}, fetch_list=[loss])
        assert exe._step == step_before  # rolled back

    # fresh state: a failure BEFORE donation (buffers left alive) must
    # surface the original error, whatever its class
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(startup)
        exe2.run_loop(2, main, feed={"dlx": xv}, fetch_list=[loss])
        step2 = exe2._step

        def boom_predispatch(*a, **k):
            raise RuntimeError("RESOURCE_EXHAUSTED: argument mismatch "
                               "before dispatch")

        exe2._loop_cache = {
            k: (traced, boom_predispatch) for k, (traced, _)
            in exe2._loop_cache.items()
        }
        with pytest.raises(RuntimeError, match="before dispatch"):
            exe2.run_loop(2, main, feed={"dlx": xv}, fetch_list=[loss])
        assert exe2._step == step2  # still rolled back
        # and the scope really is intact: a fixed cache lets it run again
        exe2._loop_cache = {}
        exe2.run_loop(2, main, feed={"dlx": xv}, fetch_list=[loss])
