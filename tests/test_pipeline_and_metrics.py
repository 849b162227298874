"""Reader combinators, PyReader device pipeline, datasets, metrics, profiler."""

import os

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers, metrics, profiler, reader
from paddle_tpu.dataset import mnist, uci_housing


def test_reader_decorators():
    r = lambda: iter(range(10))
    assert list(reader.firstn(r, 3)()) == [0, 1, 2]
    assert sorted(reader.shuffle(r, 5)()) == list(range(10))
    assert list(reader.chain(r, r)()) == list(range(10)) * 2
    assert list(reader.map_readers(lambda a: a * 2, r)()) == [i * 2 for i in range(10)]
    assert list(reader.buffered(r, 2)()) == list(range(10))
    batches = list(reader.batch(r, 4)())
    assert batches == [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9]]
    assert list(reader.batch(r, 4, drop_last=True)()) == [[0, 1, 2, 3], [4, 5, 6, 7]]
    mapped = sorted(reader.xmap_readers(lambda x: x + 1, r, 2, 4)())
    assert mapped == [i + 1 for i in range(10)]
    ordered = list(reader.xmap_readers(lambda x: x * 3, r, 3, 4, order=True)())
    assert ordered == [i * 3 for i in range(10)]


def test_feed_prefetch_stages_committed_device_arrays():
    """feed_prefetch double-buffers device_put: staged feeds come out as
    COMMITTED device arrays (the executor fast path hands them straight
    to the compiled call), in source order, value-exact."""
    import jax

    batches = [{"x": np.full((2, 3), float(i), "float32"),
                "i": np.array([i], "int64")} for i in range(6)]
    out = list(reader.feed_prefetch(lambda: iter(batches), depth=2)())
    assert len(out) == 6
    for i, feed in enumerate(out):
        assert isinstance(feed["x"], jax.Array) and feed["x"].committed
        np.testing.assert_array_equal(np.asarray(feed["x"]),
                                      batches[i]["x"])
        assert int(np.asarray(feed["i"])[0]) == i
    # depth=0 is an exact pass-through (no staging thread)
    src = lambda: iter(batches)
    assert reader.feed_prefetch(src, depth=0) is src


def test_feed_prefetch_error_and_abandon_paths():
    """The tricky halves of the combinator: a producer exception must
    reach the consumer (not a hang), and abandoning the iterator early
    must release the staging thread without deadlock."""
    import pytest

    def bad():
        yield {"x": np.zeros((1,), "float32")}
        raise ValueError("boom")

    it = reader.feed_prefetch(bad, depth=1)()
    next(it)
    with pytest.raises(ValueError, match="boom"):
        next(it)

    # abandon after one batch; depth=1 keeps the producer parked on a
    # full queue — close() must unblock it (the END sentinel is posted
    # via the same bounded put, so a full queue cannot drop it either)
    many = lambda: iter({"x": np.full((4,), float(i), "float32")}
                        for i in range(100))
    it2 = reader.feed_prefetch(many, depth=1)()
    first = next(it2)
    np.testing.assert_array_equal(np.asarray(first["x"]), np.zeros(4))
    it2.close()  # must not hang


def test_feed_prefetch_trains_identically_to_plain_feeds():
    from paddle_tpu.core import scope as scope_mod

    def build():
        x = layers.data("x", shape=[4])
        y = layers.data("y", shape=[1])
        loss = layers.mean(
            layers.square_error_cost(layers.fc(x, size=1), y))
        fluid.optimizer.SGD(0.1).minimize(loss)
        return loss

    rng = np.random.RandomState(0)
    feeds = [{"x": rng.rand(8, 4).astype("float32"),
              "y": rng.rand(8, 1).astype("float32")} for _ in range(4)]

    def train(use_prefetch):
        from paddle_tpu import framework, unique_name

        framework.switch_main_program(fluid.Program())
        framework.switch_startup_program(fluid.Program())
        unique_name.switch()
        fluid.default_main_program().random_seed = 11
        fluid.default_startup_program().random_seed = 11
        loss = build()
        scope = scope_mod.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            src = (reader.feed_prefetch(lambda: iter(feeds))()
                   if use_prefetch else iter(feeds))
            return [float(np.asarray(exe.run(
                feed=f, fetch_list=[loss])[0]).reshape(-1)[0])
                for f in src]

    np.testing.assert_allclose(train(True), train(False),
                               rtol=1e-6, atol=1e-7)


def test_pyreader_trains_mnist():
    img = layers.data("img", shape=[784])
    label = layers.data("label", shape=[1], dtype="int64")
    pred = layers.fc(layers.fc(img, 64, act="relu"), 10, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, label))
    acc = layers.accuracy(pred, label)
    fluid.optimizer.Adam(0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    train_reader = reader.batch(mnist.train(), 64, drop_last=True)
    pyreader = reader.PyReader(feed_list=[img, label], capacity=4, place=fluid.CPUPlace())

    def to_cols():
        for rows in train_reader():
            xs = np.stack([r[0] for r in rows])
            ys = np.array([[r[1]] for r in rows], "int64")
            yield {"img": xs, "label": ys}

    pyreader.decorate_batch_generator(to_cols)
    accs = []
    m = metrics.Accuracy()
    for i, feed in enumerate(pyreader()):
        lv, av = exe.run(feed=feed, fetch_list=[loss, acc])
        m.update(av, 64)
        accs.append(float(np.asarray(av)[0]))
        if i >= 40:
            break
    # synthetic mnist is separable: accuracy should climb well past chance
    assert np.mean(accs[-5:]) > 0.5, np.mean(accs[-5:])
    assert 0 <= m.eval() <= 1


def test_uci_housing_linear_regression():
    x = layers.data("x", shape=[13])
    y = layers.data("y", shape=[1])
    pred = layers.fc(x, size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    losses = []
    for epoch in range(4):
        for rows in reader.batch(uci_housing.train(), 32)():
            xs = np.stack([r[0] for r in rows])
            ys = np.stack([r[1] for r in rows])
            (lv,) = exe.run(feed={"x": xs, "y": ys}, fetch_list=[loss])
            losses.append(float(np.asarray(lv)[0]))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_metrics_precision_recall_auc():
    p = metrics.Precision()
    p.update(np.array([1, 1, 0, 1]), np.array([1, 0, 0, 1]))
    assert abs(p.eval() - 2 / 3) < 1e-6
    r = metrics.Recall()
    r.update(np.array([1, 0, 0, 1]), np.array([1, 1, 0, 1]))
    assert abs(r.eval() - 2 / 3) < 1e-6
    auc = metrics.Auc()
    preds = np.array([[0.9, 0.1], [0.2, 0.8], [0.3, 0.7], [0.6, 0.4]])
    labels = np.array([0, 1, 1, 0])
    auc.update(preds, labels)
    assert auc.eval() == 1.0


def test_auc_layer_streams_batches():
    """In-graph layers.auc accumulates stat tensors across runs and matches
    the host-side metrics.Auc on the union of the batches."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        pred = layers.data("pred", shape=[2], dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        auc_out, batch_auc_out, _states = layers.auc(
            pred, label, num_thresholds=1000, slide_steps=2)

    exe = fluid.Executor()
    exe.run(startup)
    rng = np.random.RandomState(7)
    all_p, all_l = [], []
    for _ in range(3):
        p1 = rng.rand(8, 1).astype("float32")
        p = np.concatenate([1 - p1, p1], axis=1)
        l = rng.randint(0, 2, (8, 1)).astype("int64")
        all_p.append(p)
        all_l.append(l)
        got, got_batch = exe.run(main, feed={"pred": p, "label": l},
                                 fetch_list=[auc_out, batch_auc_out])
    ref = metrics.Auc(num_thresholds=1000)
    ref.update(np.concatenate(all_p), np.concatenate(all_l).reshape(-1))
    assert abs(float(got) - ref.eval()) < 5e-2
    # batch AUC with slide_steps=2 covers only the LAST TWO batches
    ref2 = metrics.Auc(num_thresholds=1000)
    ref2.update(np.concatenate(all_p[1:]), np.concatenate(all_l[1:]).reshape(-1))
    assert abs(float(got_batch) - ref2.eval()) < 5e-2

    # slide_steps=0: the batch accumulator ALSO runs global (reference
    # semantics — batch_auc == global auc every step)
    main0 = fluid.Program()
    startup0 = fluid.Program()
    with fluid.program_guard(main0, startup0):
        pred0 = layers.data("pred0", shape=[2], dtype="float32")
        label0 = layers.data("label0", shape=[1], dtype="int64")
        g0, b0, _ = layers.auc(pred0, label0, num_thresholds=1000,
                               slide_steps=0)
    exe0 = fluid.Executor()
    exe0.run(startup0)
    for p, l in zip(all_p, all_l):
        gg, bb = exe0.run(main0, feed={"pred0": p, "label0": l},
                          fetch_list=[g0, b0])
        np.testing.assert_allclose(np.asarray(gg), np.asarray(bb),
                                   rtol=1e-6)


def test_profiler_records(tmp_path):
    path = str(tmp_path / "prof")
    x = layers.data("x", shape=[4])
    out = layers.fc(x, size=4)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    with profiler.profiler("CPU", profile_path=path):
        for _ in range(3):
            exe.run(feed={"x": np.ones((2, 4), "float32")}, fetch_list=[out])
    import json

    with open(path + ".json") as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]}
    assert "executor_run" in names


def test_in_program_py_reader_epochs_and_eof():
    """py_reader as program ops: read_file outputs feed the compiled step,
    EOFException fires at exhaustion, reset()+start() gives a new epoch
    (layers/io.py:635 + create_py_reader_op.cc contract)."""
    reader = layers.py_reader(
        capacity=8, shapes=[[-1, 10], [-1, 1]], dtypes=["float32", "int64"]
    )
    img, label = layers.read_file(reader)
    pred = layers.fc(img, 4, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, label))
    fluid.optimizer.SGD(0.1).minimize(loss)

    rng = np.random.RandomState(0)

    def gen():
        for i in range(5):
            yield [
                (rng.rand(10).astype("float32"), np.array([i % 4], "int64"))
                for _ in range(8)
            ]

    reader.decorate_paddle_reader(lambda: gen())
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    for epoch in range(3):
        reader.start()
        n = 0
        while True:
            try:
                exe.run(fetch_list=[loss])
                n += 1
            except fluid.core.EOFException:
                reader.reset()
                break
        assert n == 5, n


def test_py_reader_start_before_decorate_raises():
    reader = layers.py_reader(capacity=4, shapes=[[-1, 3]], dtypes=["float32"])
    import pytest

    with pytest.raises(RuntimeError, match="decorate"):
        reader.start()


def test_program_flops_resnet_matches_known_count():
    """Analytic FLOPs: ResNet-50 @224 is ~7.7 GFLOPs forward (2x MACs),
    ~23 GFLOPs for a training step."""
    from paddle_tpu.models.resnet import build_resnet_train_program
    from paddle_tpu.utils import flops as fu

    main, _, _, _ = build_resnet_train_program(
        image_shape=(3, 224, 224), class_dim=1000, depth=50, lr=0.1
    )
    per_img = fu.program_flops(main, batch_hint=8) / 8
    assert 20e9 < per_img < 26e9, per_img


def test_program_flops_counts_fused_attention():
    """The fused_attention op contributes its QK^T+PV FLOPs, so the fused
    transformer program counts within ~2% of the dense-bias one (the dense
    path's extra elementwise bias-add is not FLOPs-counted)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.utils import flops as flops_util

    def build(fused):
        import paddle_tpu.framework as fw
        from paddle_tpu import unique_name
        from paddle_tpu.core import scope as scope_mod

        fw.switch_main_program(fluid.Program())
        fw.switch_startup_program(fluid.Program())
        unique_name.switch()
        scope_mod._switch_scope(scope_mod.Scope())

        class HP(tfm.ModelHyperParams):
            src_vocab_size = 64
            trg_vocab_size = 64
            max_length = 16
            d_model = 32
            d_inner_hid = 64
            n_head = 4
            n_layer = 2
            dropout = 0.0
            fused_attn = fused

        main, _, _, _ = tfm.wmt_transformer_program(HP, src_len=8, trg_len=8)
        return flops_util.program_flops(main, batch_hint=4)

    dense = build(False)
    fused = build(True)
    assert dense > 0 and fused > 0
    assert abs(fused - dense) / dense < 0.02, (fused, dense)


def test_chip_peak_flops_lookup():
    import pytest

    from paddle_tpu.utils import flops as fu

    class FakeDev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    assert fu.chip_peak_flops(FakeDev()) == 197e12

    class CpuDev:
        platform = "cpu"
        device_kind = "cpu"

    assert fu.chip_peak_flops(CpuDev()) is None

    class UnknownChip:
        platform = "tpu"
        device_kind = "TPU v99 imaginary"

    # an unknown accelerator is an error, not a None that lets a run
    # report throughput under a device metric name with no peak behind it
    with pytest.raises(ValueError, match="v99 imaginary"):
        fu.chip_peak_flops(UnknownChip())


def test_py_reader_pipeline_error_surfaces():
    """A generator exception must surface as an error, not a silent short
    epoch (the reader records it and next_feed re-raises)."""
    reader = layers.py_reader(capacity=4, shapes=[[-1, 3]], dtypes=["float32"])
    (x,) = [layers.read_file(reader)]
    out = layers.scale(x, 2.0)

    def bad_gen():
        yield [(np.ones(3, "float32"),)]
        raise ValueError("boom in generator")

    reader.decorate_paddle_reader(lambda: bad_gen())
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    reader.start()
    exe.run(fetch_list=[out])  # first batch ok
    import pytest

    with pytest.raises(RuntimeError, match="pipeline failed"):
        while True:
            exe.run(fetch_list=[out])


def test_io_reader_surface_parity(tmp_path):
    """create_py_reader_by_data / random_data_generator / open_files /
    Preprocessor complete the layers.io surface; each feeds a program."""
    import pickle

    import paddle_tpu as fluid
    from paddle_tpu import layers, recordio

    # open_files over a native recordio file of pickled (x, y) tuples
    path = str(tmp_path / "data.recordio")
    w = recordio.Writer(path)
    rng = np.random.RandomState(0)
    for i in range(3):
        w.write(pickle.dumps(
            (rng.rand(4, 6).astype("float32"),
             rng.randint(0, 3, (4, 1)).astype("int64"))))
    w.close()

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.framework.program_guard(main, startup):
        reader = layers.open_files(
            [path], shapes=[[-1, 6], [-1, 1]], dtypes=["float32", "int64"])
        x, y = layers.read_file(reader)
        out = layers.reduce_sum(x)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        reader.start()
        seen = 0
        while True:
            try:
                exe.run(main, fetch_list=[out])
                seen += 1
            except Exception:
                break
        assert seen == 3, seen

    # random_data_generator + Preprocessor (transform visible in outputs)
    main2 = fluid.Program()
    startup2 = fluid.Program()
    with fluid.framework.program_guard(main2, startup2):
        r2 = layers.random_data_generator(0.0, 1.0, shapes=[[-1, 4]])
        p = layers.Preprocessor(r2)
        with p.block():
            p.set_transform(lambda a: a + 100.0)
        xv = layers.read_file(r2)
        m = layers.reduce_min(xv)
    exe2 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe2.run(startup2)
        r2.start()
        (mn,) = exe2.run(main2, fetch_list=[m])
        assert float(np.asarray(mn)) >= 100.0  # transform applied
        r2.reset()

    # create_py_reader_by_data mirrors data-var shapes
    main3 = fluid.Program()
    startup3 = fluid.Program()
    with fluid.framework.program_guard(main3, startup3):
        dx = layers.data("cprd_x", shape=[5])
        r3 = layers.create_py_reader_by_data(8, [dx])
        x3 = layers.read_file(r3)
        assert tuple(x3.shape[1:]) == (5,)


def test_preprocessor_rows_reader_path():
    """Preprocessor also transforms decorate_paddle_reader (rows-style)
    inputs — columnized before fn, never silently dropped."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.framework.program_guard(main, startup):
        r = layers.py_reader(capacity=4, shapes=[[-1, 3]], dtypes=["float32"])
        p = layers.Preprocessor(r)
        with p.block():
            p.set_transform(lambda a: a + 100.0)
        xv = layers.read_file(r)
        m = layers.reduce_min(xv)

    def rows():
        rng = np.random.RandomState(0)
        for _ in range(2):
            yield [(rng.rand(3).astype("float32"),) for _ in range(4)]

    r.decorate_paddle_reader(rows)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        r.start()
        (mn,) = exe.run(main, fetch_list=[m])
        assert float(np.asarray(mn)) >= 100.0
        r.reset()


def test_print_layer_survives_dce(capfd):
    """layers.Print with a discarded return still prints (print op is a
    side effect, never pruned)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x = layers.data("pr_x", shape=[2])
        layers.Print(x, message="PRINTME")
        out = layers.reduce_sum(x)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={"pr_x": np.ones((1, 2), "float32")},
                fetch_list=[out])
    captured = capfd.readouterr()
    assert "PRINTME" in captured.out + captured.err


def test_create_custom_reader_semantics_via_decorators():
    """Closes the create_custom_reader (Preprocessor) op-coverage entry
    with PROOF, not a table comment: the reference example
    (io.py:1080 — img/2, lbl+1 applied in-reader) is reproduced two ways
    and both match a manual transform of the same stream:
    (a) reader.map_readers decorator feeding the program, and
    (b) layers.Preprocessor on a py_reader (in-pipeline stage)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers, reader as rdr

    rng = np.random.RandomState(7)
    batches = [(rng.rand(4, 3).astype("float32"),
                rng.randint(0, 5, (4, 1)).astype("int64"))
               for _ in range(3)]

    def base():
        for b in batches:
            yield b

    # (a) decorator path: map_readers applies the preprocessing (one
    # item per reader, so the (img, lbl) batch arrives as one tuple)
    mapped = rdr.map_readers(lambda b: (b[0] / 2.0, b[1] + 1), base)
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.framework.program_guard(main, startup):
        img = layers.data("ccr_img", shape=[4, 3], append_batch_size=False)
        lbl = layers.data("ccr_lbl", shape=[4, 1], dtype="int64",
                          append_batch_size=False)
        s = layers.reduce_sum(img) + layers.cast(layers.reduce_sum(lbl),
                                                 "float32")
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        got = [float(np.asarray(exe.run(
            main, feed={"ccr_img": i, "ccr_lbl": l}, fetch_list=[s])[0]))
            for i, l in mapped()]
    want = [float(i.sum() / 2.0 + (l + 1).sum()) for i, l in batches]
    np.testing.assert_allclose(got, want, rtol=1e-5)

    # (b) in-pipeline stage: Preprocessor on a py_reader, same transform
    main2, startup2 = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main2, startup2):
        r = layers.py_reader(capacity=4, shapes=[[-1, 4, 3], [-1, 4, 1]],
                             dtypes=["float32", "int64"])
        p = layers.Preprocessor(r)
        with p.block():
            p.set_transform(lambda img, lbl: (img / 2.0, lbl + 1))
        iv, lv = layers.read_file(r)
        s2 = layers.reduce_sum(iv) + layers.cast(layers.reduce_sum(lv),
                                                 "float32")

    def feed_gen():
        for i, l in batches:
            yield i[None], l[None]

    r.decorate_tensor_provider(feed_gen)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup2)
        r.start()
        got2 = [float(np.asarray(exe.run(main2, fetch_list=[s2])[0]))
                for _ in batches]
        r.reset()
    np.testing.assert_allclose(got2, want, rtol=1e-5)
