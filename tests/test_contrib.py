"""contrib: Trainer/Inferencer, checkpoint-resume, QAT transpiler,
BeamSearchDecoder, memory/op-freq utilities."""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.contrib import (
    BeginStepEvent,
    CheckpointConfig,
    EndStepEvent,
    Inferencer,
    Trainer,
    memory_usage,
    op_freq_statistic,
)
from paddle_tpu.contrib.decoder import BeamSearchDecoder
from paddle_tpu.contrib.quantize import QuantizeTranspiler


def _train_func():
    x = layers.data("x", shape=[4])
    y = layers.data("y", shape=[1])
    pred = layers.fc(layers.fc(x, size=8, act="relu"), size=1)
    return layers.mean(layers.square_error_cost(pred, y))


def _infer_func():
    x = layers.data("x", shape=[4])
    return layers.fc(layers.fc(x, size=8, act="relu"), size=1)


def _reader():
    rng = np.random.RandomState(3)
    x = rng.rand(16, 4).astype("float32")
    w = np.array([[1.0], [-2.0], [3.0], [0.5]], dtype=np.float32)
    y = x @ w

    def gen():
        for _ in range(8):
            yield {"x": x, "y": y}

    return gen


def test_trainer_events_and_infer(tmp_path):
    events = []

    def handler(ev):
        events.append(type(ev).__name__)
        if isinstance(ev, EndStepEvent):
            events.append(float(np.ravel(ev.metrics[0])[0]))

    trainer = Trainer(_train_func, lambda: fluid.optimizer.Adam(0.05))
    trainer.train(num_epochs=2, event_handler=handler, reader=_reader(), feed_order=["x", "y"])
    losses = [e for e in events if isinstance(e, float)]
    assert losses[-1] < losses[0]
    assert "BeginEpochEvent" in events and "EndEpochEvent" in events

    param_path = str(tmp_path / "params")
    trainer.save_params(param_path)
    inferencer = Inferencer(_infer_func, param_path)
    out = inferencer.infer({"x": np.ones((2, 4), "float32")})
    assert np.asarray(out[0]).shape == (2, 1)


def test_checkpoint_resume(tmp_path):
    ckpt = str(tmp_path / "ckpt")

    cfg = CheckpointConfig(ckpt, max_num_checkpoints=2, step_interval=3)
    t1 = Trainer(_train_func, lambda: fluid.optimizer.SGD(0.1), checkpoint_config=cfg)
    t1.train(2, lambda ev: None, _reader(), ["x", "y"])
    serials = sorted(os.listdir(ckpt))
    assert len(serials) <= 2  # pruning kept the max_num limit
    w_after = np.array(t1.scope.find_var("fc_0.w_0"))

    # a fresh trainer resumes from the newest serial: params match and the
    # epoch pointer advanced past the completed epochs
    cfg2 = CheckpointConfig(ckpt, max_num_checkpoints=2, step_interval=3)
    t2 = Trainer(_train_func, lambda: fluid.optimizer.SGD(0.1), checkpoint_config=cfg2)
    np.testing.assert_allclose(
        np.array(t2.scope.find_var("fc_0.w_0")), w_after, rtol=1e-6
    )
    assert cfg2.epoch_id == 2
    # training for the same num_epochs is a no-op (already done)
    steps = []
    t2.train(2, lambda ev: steps.append(ev), _reader(), ["x", "y"])
    assert not any(isinstance(ev, EndStepEvent) for ev in steps)


def test_quantize_transpiler_qat_and_freeze():
    x = layers.data("x", shape=[8])
    y = layers.data("y", shape=[1], dtype="int64")
    pred = layers.fc(layers.fc(x, size=16, act="relu"), size=4, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    main = fluid.default_main_program()

    qt = QuantizeTranspiler(activation_quantize_type="moving_average_abs_max")
    qt.training_transpile(main)
    types = [op.type for op in main.global_block().ops]
    assert any(t.startswith("fake_quantize") for t in types)

    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    xv = rng.rand(32, 8).astype("float32")
    yv = rng.randint(0, 4, (32, 1)).astype("int64")
    l0 = exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])[0]
    for _ in range(20):
        l1 = exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss])[0]
    assert float(np.ravel(l1)[0]) < float(np.ravel(l0)[0])  # QAT still trains

    # freeze for inference: weights pre-quantized, act scales pinned
    test_prog = main.clone(for_test=True)
    (q_ref,) = exe.run(program=test_prog, feed={"x": xv}, fetch_list=[pred.name])
    frozen = qt.freeze_program(main.clone(for_test=True))
    ftypes = [op.type for op in frozen.global_block().ops]
    assert "fake_quantize_abs_max" not in ftypes  # weight quant folded
    (q_frozen,) = exe.run(program=frozen, feed={"x": xv}, fetch_list=[pred.name])
    np.testing.assert_allclose(
        np.asarray(q_frozen), np.asarray(q_ref), rtol=1e-3, atol=1e-4
    )


def test_beam_search_decoder_toy():
    """Deterministic toy LM: token t always followed by (t+1) % vocab with
    prob ~1 -> greedy path from start=1 is 2,3,4,0(end)."""
    vocab = 5

    def step_fn(tokens, states):
        logp = np.full((tokens.size, vocab), -10.0, np.float32)
        nxt = (tokens + 1) % vocab
        logp[np.arange(tokens.size), nxt] = -0.1
        return logp, states

    dec = BeamSearchDecoder(step_fn, beam_size=2, start_token=1, end_token=0, max_len=8)
    out, scores = dec.decode(batch_size=2)
    np.testing.assert_array_equal(out[0, 0], [2, 3, 4, 0])
    np.testing.assert_array_equal(out[1, 0], [2, 3, 4, 0])
    assert scores.shape == (2, 2)


def test_memory_usage_and_op_freq():
    _train_func()
    prog = fluid.default_main_program()
    low, high = memory_usage(prog, batch_size=32)
    assert 0 < low <= high
    singles, pairs = op_freq_statistic(prog)
    assert singles.get("mul", 0) >= 2 or singles.get("matmul", 0) >= 2


def test_fp16_inference_rewrite_matches_f32():
    """rewrite_fp16 (contrib/float16 transpiler parity): fp16-cast
    inference program stays close to the f32 reference."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.contrib.mixed_precision import rewrite_fp16

    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        startup.random_seed = 3
        x = layers.data("x", shape=[16])
        y = layers.fc(layers.fc(x, 32, act="relu"), 4, act="softmax")
    xv = np.random.RandomState(0).rand(4, 16).astype("float32")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        (ref,) = exe.run(main, feed={"x": xv}, fetch_list=[y])
        n = rewrite_fp16(main)
        assert n >= 2
        (out,) = exe.run(main, feed={"x": xv}, fetch_list=[y])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-3)
    assert any("@FP16" in op.outputs.get("Out", [""])[0]
               for op in main.global_block().ops if op.type == "cast")


def test_amp_collapses_redundant_cast_roundtrips():
    """Consecutive matmul-class ops stop bouncing through f32: the
    bf16->f32->bf16 pair between two fc matmuls collapses with IDENTICAL
    numerics (half->f32->half is exact)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.contrib.mixed_precision import rewrite_bf16

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.framework.program_guard(main, startup):
            startup.random_seed = 9
            x = layers.data("x", shape=[16])
            y = layers.fc(layers.fc(x, 32, bias_attr=False), 4,
                          bias_attr=False)
        return main, startup, y

    xv = np.random.RandomState(1).rand(4, 16).astype("float32")

    main, startup, y = build()
    rewrite_bf16(main)
    # the second mul's data input must read the FIRST mul's raw bf16
    # output directly (the f32 roundtrip between the two muls collapsed)
    muls = [op for op in main.global_block().ops if op.type == "mul"]
    assert len(muls) == 2
    assert muls[1].inputs["X"][0].endswith("@RAW_BF16"), muls[1].inputs

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        (out,) = exe.run(main, feed={"x": xv}, fetch_list=[y])

    # reference: same seeds, uncollapsed semantics == plain bf16 math
    main2, startup2, y2 = build()
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup2)
        (ref,) = exe.run(main2, feed={"x": xv}, fetch_list=[y2])
    # bf16 fc chain vs f32 chain: close but not equal; the collapsed
    # program must match the f32 reference at bf16 tolerance
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_amp_trunk_keeps_bf16_through_bn_relu_pool():
    """propagate_half_through_trunk: dtype-transparent ops (batch_norm /
    relu / pool2d / same-shape elementwise_add) run in bf16 when fed from
    half cast-backs, BN statistics stay f32, and training parity with the
    f32 program holds at bf16 tolerance."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.contrib.mixed_precision import rewrite_bf16

    def run(amp):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.framework.program_guard(main, startup):
            startup.random_seed = 11
            img = layers.data("img", shape=[3, 16, 16])
            label = layers.data("label", shape=[1], dtype="int64")
            c1 = layers.conv2d(img, 8, 3, padding=1, act=None,
                               bias_attr=False)
            b1 = layers.batch_norm(c1, act="relu")
            c2 = layers.conv2d(b1, 8, 3, padding=1, act=None,
                               bias_attr=False)
            b2 = layers.batch_norm(c2, act=None)
            res = layers.elementwise_add(b1, b2, act="relu")
            p = layers.pool2d(res, pool_size=2, pool_type="avg",
                              global_pooling=True)
            pred = layers.fc(p, 10, act="softmax")
            loss = layers.mean(layers.cross_entropy(pred, label))
            if amp:
                rewrite_bf16(main)
                blk = main.global_block()
                for t, slot in (("batch_norm", "X"), ("relu", "X"),
                                ("pool2d", "X"), ("elementwise_add", "X")):
                    flips = [op for op in blk.ops if op.type == t
                             and "@RAW_BF16" in op.inputs[slot][0]]
                    assert flips, "no %s flipped to bf16" % t
                # BN running-stat outputs stay on their f32 names
                bn = [op for op in blk.ops if op.type == "batch_norm"][0]
                assert not bn.outputs["MeanOut"][0].endswith("@RAW_BF16")
            fluid.optimizer.Momentum(0.05, momentum=0.9).minimize(loss)
        rng = np.random.RandomState(3)
        x = rng.rand(16, 3, 16, 16).astype("float32")
        y = rng.randint(0, 10, (16, 1)).astype("int64")
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            losses = [
                float(np.ravel(exe.run(
                    main, feed={"img": x, "label": y},
                    fetch_list=[loss])[0])[0])
                for _ in range(6)
            ]
            # moving mean updated, in f32, through the flipped BN
            # (resolve the name from the op: unique suffixes differ
            # between the two runs sharing this process)
            bn0 = [op for op in main.global_block().ops
                   if op.type == "batch_norm"][0]
            mm = np.asarray(scope.find_var(bn0.inputs["Mean"][0]))
        assert mm.dtype == np.float32 and np.any(mm != 0)
        return losses

    f32 = run(False)
    amp = run(True)
    assert amp[-1] < amp[0]
    np.testing.assert_allclose(amp, f32, rtol=0.2, atol=0.05)


# --- the ops that only move values (split, concat, expand) ---------------------
def _split_net():
    """One input, three outputs: one feeds a transparent op, one a
    computing op, one a bfloat16-list op."""
    x = layers.data("x", shape=[6, 8])
    a, b, c = layers.split(
        layers.fc(x, 24, num_flatten_dims=2, bias_attr=False), [8, 8, 8],
        dim=-1)
    a = layers.reshape(a, [-1, 6, 2, 4])
    b = layers.swish(b)
    c = layers.fc(c, 8, num_flatten_dims=2, bias_attr=False)
    return layers.mean(layers.reshape(a, [-1, 6, 8]) * b + c)


def _concat_net(second_is_half):
    x = layers.data("x", shape=[6, 8])
    a = layers.fc(x, 8, num_flatten_dims=2, bias_attr=False)
    b = (layers.fc(x, 4, num_flatten_dims=2, bias_attr=False)
         if second_is_half else layers.scale(x, scale=0.5))
    y = layers.fc(layers.concat([a, b], axis=-1), 8, num_flatten_dims=2,
                  bias_attr=False)
    return layers.mean(y)


def _expand_net():
    x = layers.data("x", shape=[6, 8])
    a = layers.reshape(layers.fc(x, 8, num_flatten_dims=2, bias_attr=False),
                       [-1, 6, 1, 8])
    y = layers.fc(layers.reshape(layers.expand(a, [1, 1, 3, 1]),
                                 [-1, 6, 24]), 8, num_flatten_dims=2,
                  bias_attr=False)
    return layers.mean(y)


def _mamba2_shaped_net():
    """fc -> split -> causal_conv -> split -> reshape / transpose ->
    mamba2_scan, gated by the float32 z: the shape of
    models/nemotron_h._mamba2 at toy widths."""
    from paddle_tpu.initializer import Constant

    heads, p, g, n, t = 2, 4, 1, 4, 8
    inner = heads * p

    def lead(y, count, width):
        return layers.transpose(layers.reshape(y, [-1, t, count, width]),
                                [0, 2, 1, 3])

    def number_a_head(name, value):
        return layers.create_parameter(
            [heads], "float32", attr=fluid.ParamAttr(
                name=name, initializer=Constant(value)))

    x = layers.data("x", shape=[t, 8])
    z, xbc, dt = layers.split(
        layers.fc(x, 2 * inner + 2 * g * n + heads, num_flatten_dims=2,
                  bias_attr=False), [inner, inner + 2 * g * n, heads], dim=-1)
    xbc = layers.causal_conv(xbc, 4, act="silu")
    xs, bm, cm = layers.split(xbc, [inner, g * n, g * n], dim=-1)
    dt = layers.softplus(layers.elementwise_add(
        layers.cast(layers.transpose(dt, [0, 2, 1]), "float32"),
        number_a_head("dt_bias", 0.5), axis=1))
    y = layers.mamba2_scan(
        lead(xs, heads, p), dt,
        layers.scale(layers.exp(number_a_head("a_log", 0.1)), scale=-1.0),
        lead(bm, g, n), lead(cm, g, n), number_a_head("skip", 1.0))
    y = layers.elementwise_mul(
        layers.reshape(layers.transpose(y, [0, 2, 1, 3]), [-1, t, inner]),
        layers.swish(z))
    return layers.mean(layers.fc(y, 8, num_flatten_dims=2, bias_attr=False))


_ROUNDINGS_KEPT = "--xla_allow_excess_precision=false"
_MOVE_NETS = {
    "split": (_split_net, {"split": 1}),
    "concat": (lambda: _concat_net(True), {"concat": 1}),
    "concat_with_a_float32_input": (lambda: _concat_net(False), {}),
    "expand": (_expand_net, {"expand": 1}),
    "mamba2_block": (_mamba2_shaped_net, {"split": 2}),
}


def _amp_program(net, hold_out, monkeypatch):
    """The net under rewrite_bf16 with its backward, its loss and every
    parameter gradient on one batch; `hold_out`: the three move ops taken
    out of the pass's table first (what the pass did before it knew them)."""
    from paddle_tpu.contrib import mixed_precision as mp

    main, startup = fluid.Program(), fluid.Program()
    with monkeypatch.context() as patch, \
            fluid.framework.program_guard(main, startup), \
            fluid.unique_name.guard():
        if hold_out:
            patch.setattr(mp, "_TRANSPARENT_OPS", {
                k: v for k, v in mp._TRANSPARENT_OPS.items()
                if k not in mp._MOVE_OPS})
        startup.random_seed = 5
        loss = net()
        mp.rewrite_bf16(main)
        fluid.backward.append_backward(loss)
    params = sorted(p.name for p in main.global_block().all_parameters())
    x = np.random.RandomState(7).randn(3, *main.global_block().var(
        "x").shape[1:]).astype("float32")
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out = exe.run(main, feed={"x": x}, fetch_list=[loss] + [
            main._grad_names[n] for n in params])
    return main, dict(zip(["loss"] + params, out))


@pytest.mark.parametrize("case", sorted(_MOVE_NETS))
def test_amp_move_ops_run_in_the_dtype_their_data_arrives_in(
        case, monkeypatch):
    """split, concat and expand read `@RAW_BF16` names where every input
    is the cast-back of a half tensor, and write half vars of their own; a
    concat with one float32 input stays float32; the pass leaves the count
    by op type on the Program."""
    from paddle_tpu import analysis
    from paddle_tpu.contrib import mixed_precision as mp

    net, want = _MOVE_NETS[case]
    main, _ = _amp_program(net, False, monkeypatch)
    block = main.global_block()
    flipped = getattr(main, "_amp_half_flipped", {})
    assert {t: flipped.get(t, 0) for t in mp._MOVE_OPS} == {
        t: want.get(t, 0) for t in mp._MOVE_OPS}
    for op in block.ops:
        if op.type not in mp._MOVE_OPS:
            continue
        names = op.inputs["X"] + op.outputs["Out"]
        if want:
            assert all(n.endswith("@RAW_BF16") for n in names), names
            assert {str(block.var(n).dtype) for n in names} == {"bfloat16"}
        else:
            assert not any("@RAW_BF16" in n for n in names), names
            assert {str(block.var(n).dtype) for n in names} == {"float32"}
    assert not [d for d in analysis.verify_program(main) if d.is_error]
    if case == "split":
        # the transparent consumer flips, the bfloat16-list consumer reads
        # the half output with no cast between, the computing consumer
        # reads the float32 cast-back
        (split,) = [op for op in block.ops if op.type == "split"]
        a, b, c = split.outputs["Out"]
        readers = {n: sorted(
            op.type for op in block.ops
            if op.attrs.get("op_role", "forward") == "forward"
            and n in op.input_arg_names()) for n in (a, b, c)}
        assert readers[a] == ["cast", "reshape2"]
        assert readers[b] == ["cast"]
        assert readers[c] == ["cast", "mul"]
        (swish,) = [op for op in block.ops if op.type == "swish"]
        assert str(block.var(swish.inputs["X"][0]).dtype) == "float32"


@pytest.mark.parametrize("case", sorted(_MOVE_NETS))
def test_amp_move_ops_change_no_value(case, monkeypatch):
    """Rounding to bfloat16 commutes with a slice, a concatenation and a
    tiling: the loss and every parameter gradient are EQUAL, array for
    array, to those of the same Program rewritten with the three ops held
    out of the table."""
    net, _ = _MOVE_NETS[case]
    _, got = _amp_program(net, False, monkeypatch)
    held, want = _amp_program(net, True, monkeypatch)
    assert not getattr(held, "_amp_half_flipped", {}).keys() & {
        "split", "concat", "expand"}
    assert sorted(got) == sorted(want) and len(got) > 1
    # with every rounding kept (the test below) the two Programs fuse into
    # other float32 reductions: the last bit of a float32 sum may differ,
    # 2^12 times under one bfloat16 ulp
    rtol = 2.0 ** -20 if _ROUNDINGS_KEPT in os.environ.get(
        "XLA_FLAGS", "") else 0.0
    for name in want:
        assert np.asarray(got[name]).dtype == np.float32, name
        assert np.any(np.asarray(want[name]) != 0), name
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]), rtol=rtol, atol=0,
                                   err_msg=name)


def test_amp_move_ops_change_no_value_with_every_rounding_kept():
    """XLA may drop a float32 -> bfloat16 -> float32 pair
    (`xla_allow_excess_precision`, on by default: on this host the
    cast-back of a matmul's bfloat16 result then reads the float32
    accumulator), on BOTH sides of the comparison above.  In a process of
    its own with every rounding kept, the equality is the Program's."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "") + " " + _ROUNDINGS_KEPT))
    out = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.abspath(__file__), "-q",
         "-p", "no:cacheprovider", "-k",
         "test_amp_move_ops_change_no_value and not rounding_kept"],
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))), capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "%d passed" % len(_MOVE_NETS) in out.stdout, out.stdout[-500:]


def test_amp_trunk_keeps_bf16_through_transformer_chain():
    """The transformer-block chain (mul -> broadcast bias add -> reshape2
    -> transpose2 -> dropout -> layer_norm -> residual add) stays bf16:
    bias adds flip with the bias cast to half in place, layer_norm flips
    with f32-internal statistics, and a same-shape f32 activation add
    does NOT flip (keeps the f32 contract)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.contrib.mixed_precision import rewrite_bf16

    def run(amp):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.framework.program_guard(main, startup):
            startup.random_seed = 21
            x = layers.data("x", shape=[8, 32])  # [B, T, D]
            label = layers.data("label", shape=[8, 1], dtype="int64")
            h = layers.fc(x, 32, num_flatten_dims=2, act=None)  # bias add
            h = layers.reshape(h, [-1, 8, 4, 8])
            h = layers.transpose(h, [0, 2, 1, 3])
            h = layers.transpose(h, [0, 2, 1, 3])
            h = layers.reshape(h, [-1, 8, 32])
            h = layers.dropout(h, dropout_prob=0.1, seed=5)
            h = layers.layer_norm(h)
            # sigmoid is NOT dtype-transparent: its f32 output feeding an
            # add must keep the add f32 (no silent activation truncation)
            gate = layers.sigmoid(layers.fc(x, 32, num_flatten_dims=2,
                                            bias_attr=False))
            h = layers.elementwise_add(h, gate)
            logits = layers.fc(h, 10, num_flatten_dims=2)
            loss = layers.mean(
                layers.softmax_with_cross_entropy(logits, label))
            if amp:
                rewrite_bf16(main)
                blk = main.global_block()
                for t, slot in (("reshape2", "X"), ("transpose2", "X"),
                                ("dropout", "X"), ("layer_norm", "X")):
                    flips = [op for op in blk.ops if op.type == t
                             and "@RAW_BF16" in op.inputs[slot][0]]
                    assert flips, "no %s flipped to bf16" % t
                # the FC bias add flipped, reading the bias through an
                # in-place half cast
                bias_adds = [
                    op for op in blk.ops if op.type == "elementwise_add"
                    and op.inputs["Y"][0].endswith("@BIAS_BF16")
                ]
                assert bias_adds, "no bias add flipped"
                # the sigmoid-gate add stayed f32 (Y is a same-shape f32
                # activation, not a bias)
                gate_adds = [
                    op for op in blk.ops if op.type == "elementwise_add"
                    and not op.inputs["Y"][0].endswith("@BIAS_BF16")
                    and not op.inputs["Y"][0].endswith("@RAW_BF16")
                    and "@" not in op.inputs["X"][0]
                ]
                assert gate_adds, "gate add was wrongly flipped"
            fluid.optimizer.SGD(0.05).minimize(loss)
        rng = np.random.RandomState(7)
        xv = rng.rand(4, 8, 32).astype("float32")
        yv = rng.randint(0, 10, (4, 8, 1)).astype("int64")
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            return [
                float(np.ravel(exe.run(
                    main, feed={"x": xv, "label": yv},
                    fetch_list=[loss])[0])[0])
                for _ in range(5)
            ]

    f32 = run(False)
    amp = run(True)
    assert amp[-1] < amp[0]
    np.testing.assert_allclose(amp, f32, rtol=0.1, atol=0.05)
