"""Optimization transpiler layer: HBM-budgeted remat, the generalized
inference pass pipeline, the program autotuner, and the memory_optimize
aliasing contracts (docs/PERFORMANCE.md "Optimization transpiler
layer")."""

import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers


SEQ = 8


def _tiny_hp():
    from paddle_tpu.models import transformer as tfm

    class HP(tfm.ModelHyperParams):
        max_length = 16
        d_model = 16
        d_inner_hid = 32
        n_layer = 2
        n_head = 2
        src_vocab_size = 50
        trg_vocab_size = 50
        fused_attn = True

    return HP


def _build_tfm(budget=0, is_test=False):
    from paddle_tpu.models import transformer as tfm

    flags.set_flags({"hbm_budget_bytes": budget})
    try:
        return tfm.wmt_transformer_program(
            _tiny_hp(), src_len=SEQ, trg_len=SEQ, is_test=is_test)
    finally:
        flags.set_flags({"hbm_budget_bytes": 0})


def _run_steps(main, startup, fetches, n=3, seed=7):
    from paddle_tpu.models import transformer as tfm

    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        startup.random_seed = seed
        exe.run(startup)
        batch = tfm.make_fake_batch(4, SEQ, SEQ, _tiny_hp(), seed=0)
        for _ in range(n):
            out = exe.run(main, feed=batch, fetch_list=fetches)
            losses.append(np.asarray(out[0]).copy())
    return losses


# ---------------------------------------------------------------------------
# remat: estimator + budgeted pass
# ---------------------------------------------------------------------------
def test_remat_cuts_peak_at_forcing_budget_and_losses_bit_exact():
    """THE acceptance bar: at a budget that forces recompute, the
    transformer builder's estimated peak activation bytes drop >= 40%,
    and training losses are bit-identical to the same partitioned
    program with checkpointing disabled (policy=everything_saveable —
    identical vjp structure, nothing recomputed), i.e. the RECOMPUTE
    decision changes scheduling only, never math.  Vs the UNPARTITIONED
    program: step-0 forward is bit-identical (identical fwd ops, RNG
    streams pinned); later steps agree to float-roundoff (the
    segment-level vjp may reassociate gradient fan-in sums by a ULP)."""
    main_r, st, _, fetches = _build_tfm(budget=1)  # 1 byte: force max
    rep = main_r._remat_report
    assert rep["segments_marked"] >= 2
    cut = 1.0 - rep["after_bytes"] / rep["before_bytes"]
    assert cut >= 0.40, rep

    twin = main_r.clone()
    for op in twin.global_block().ops:
        if op.type == "recompute":
            op.attrs["policy"] = "everything_saveable"
    twin._bump_version()

    l_remat = _run_steps(main_r, st, fetches, n=2)
    l_twin = _run_steps(twin, st, fetches, n=2)
    assert all(np.array_equal(a, b) for a, b in zip(l_remat, l_twin)), (
        l_remat, l_twin)

    main_0, st_0, _, f_0 = _build_tfm(budget=0)
    assert not any(op.type == "recompute"
                   for op in main_0.global_block().ops)
    l_base = _run_steps(main_0, st_0, f_0, n=2)
    assert np.array_equal(l_base[0], l_remat[0])
    for a, b in zip(l_base, l_remat):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=0)


@pytest.mark.slow
def test_remat_partial_budget_pins_rng_streams():
    """PARTIAL marking: a budget met by a SUBSET of segments shifts the
    positions of later UNWRAPPED ops — pin_rng_streams must keep every
    dropout's draw identical to the unremat program (the tiny HP has
    dropout=0.1 live, so an unpinned stream flips step-0's loss).
    Rides the ci.sh transpiler lane (-m \"\")."""
    main_f, _, _, _ = _build_tfm(budget=1)  # learn the before/after span
    rep = main_f._remat_report
    mid = (rep["before_bytes"] + rep["after_bytes"]) // 2
    main_p, st_p, _, f_p = _build_tfm(budget=mid)
    rep_p = main_p._remat_report
    assert 0 < rep_p["segments_marked"] < rep["segments_marked"], rep_p
    assert rep_p["fits"] and rep_p["after_bytes"] <= mid, rep_p
    main_0, st_0, _, f_0 = _build_tfm(budget=0)
    l_part = _run_steps(main_p, st_p, f_p, n=1)
    l_base = _run_steps(main_0, st_0, f_0, n=1)
    assert np.array_equal(l_part[0], l_base[0]), (l_part[0], l_base[0])


def test_estimator_monotone_in_marked_segments():
    """More recomputed segments can only lower (never raise) the
    estimated fwd+bwd peak — the property budgeted greedy marking and
    its binary search rely on."""
    from paddle_tpu.transpiler.remat import detect_segments, wrap_segment
    from paddle_tpu.utils import memory_analysis as ma

    main, _, feeds, fetches = _build_tfm(is_test=True)
    loss = fetches[0].name
    specs = ma.program_feed_specs(main, feeds, batch_hint=4)
    segments = detect_segments(main)
    assert len(segments) >= 4, segments

    peaks = []
    for k in (0, 2, len(segments) - 1):
        clone = main.clone()
        cblock = clone.global_block()
        runs = []
        for (a, b) in segments[:-1][:k]:
            runs.append((a, b - a))
        for a, ln in sorted(runs, reverse=True):
            wrap_segment(clone, cblock.ops[a:a + ln], protect=(loss,))
        # fwd+BWD: remat trades backward residuals for recompute — a
        # forward-only trace has no residuals and nothing to cut
        peaks.append(ma.estimate_peak_activation_bytes(
            clone, specs, loss, wrt="params")["peak_bytes"])
    assert peaks[0] >= peaks[1] >= peaks[2], peaks
    assert peaks[2] < peaks[0], peaks


def test_jaxpr_peak_bytes_counts_liveness_not_totals():
    """The walk reports simultaneously-live bytes: a chain of N equal
    buffers peaks near a couple of buffers, not N of them."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.utils.memory_analysis import jaxpr_peak_bytes

    def chain(x):
        for _ in range(10):
            x = jnp.tanh(x) + 1.0
        return x

    x = jnp.zeros((128, 128), jnp.float32)
    peak, largest = jaxpr_peak_bytes(jax.make_jaxpr(chain)(x))
    assert largest == 128 * 128 * 4
    assert peak <= 3 * largest, peak  # live set, not sum of all temps


def test_remat_pass_registry_form_marks_segments():
    from paddle_tpu.transpiler import apply_pass

    main, _, _, fetches = _build_tfm(is_test=True)
    main._protected_fetch_names = (fetches[0].name,)
    apply_pass(main, "remat_pass")
    n = sum(1 for op in main.global_block().ops
            if op.type == "recompute")
    assert n >= 2
    assert main._remat_marked_count == n


# ---------------------------------------------------------------------------
# inference transpiler sub-passes
# ---------------------------------------------------------------------------
def _startup_run(startup, scope, seed=3):
    exe = fluid.Executor(fluid.CPUPlace())
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    return exe


def test_bn_fold_conv_bn_relu_parity():
    """conv+BN+relu: the BN folds into the conv weights (>= 1 op gone),
    the relu survives, outputs match at rtol 1e-5."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        img = layers.data("cbr_img", shape=[3, 8, 8])
        c = layers.conv2d(img, num_filters=4, filter_size=3, act=None)
        bn = layers.batch_norm(c, is_test=True)
        out = layers.relu(bn)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = _startup_run(startup, scope)
        scope.set("batch_norm_0.w_1",
                  np.random.RandomState(1).rand(4).astype("float32"))
        scope.set("batch_norm_0.w_2",
                  (np.random.RandomState(2).rand(4) + 0.5).astype("float32"))
        x = np.random.RandomState(0).rand(2, 3, 8, 8).astype("float32")
        (ref,) = exe.run(main, feed={"cbr_img": x}, fetch_list=[out],
                         scope=scope)
        n_before = len(main.global_block().ops)
        from paddle_tpu.transpiler import apply_pass

        apply_pass(main, "bn_fold_pass", scope=scope)
        types = [op.type for op in main.global_block().ops]
        assert "batch_norm" not in types, types
        assert "relu" in types, types
        assert len(types) <= n_before - 1
        (got,) = exe.run(main, feed={"cbr_img": x}, fetch_list=[out],
                         scope=scope)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_bn_fold_fc_bn_parity():
    """fc+BN (the per-out-column fold, new in the generalized pass):
    outputs match at rtol 1e-5 with the BN op gone."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x_in = layers.data("fcbn_x", shape=[6])
        h = layers.fc(x_in, size=5, act=None)
        out = layers.batch_norm(h, is_test=True)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = _startup_run(startup, scope)
        scope.set("batch_norm_0.w_1",
                  np.random.RandomState(4).rand(5).astype("float32"))
        scope.set("batch_norm_0.w_2",
                  (np.random.RandomState(5).rand(5) + 0.5).astype("float32"))
        x = np.random.RandomState(0).rand(3, 6).astype("float32")
        (ref,) = exe.run(main, feed={"fcbn_x": x}, fetch_list=[out],
                         scope=scope)
        from paddle_tpu.transpiler import apply_pass

        apply_pass(main, "bn_fold_pass", scope=scope)
        types = [op.type for op in main.global_block().ops]
        assert "batch_norm" not in types, types
        (got,) = exe.run(main, feed={"fcbn_x": x}, fetch_list=[out],
                         scope=scope)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_bn_fold_scale_chain_parity():
    """conv -> pure scale -> BN (the scale-chain form): both the scale
    and the BN fold into the conv weights."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        img = layers.data("sc_img", shape=[2, 6, 6])
        c = layers.conv2d(img, num_filters=3, filter_size=3,
                          act=None, bias_attr=False)
        s = layers.scale(c, scale=1.7)
        out = layers.batch_norm(s, is_test=True)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = _startup_run(startup, scope)
        scope.set("batch_norm_0.w_1",
                  np.random.RandomState(6).rand(3).astype("float32"))
        scope.set("batch_norm_0.w_2",
                  (np.random.RandomState(7).rand(3) + 0.5).astype("float32"))
        x = np.random.RandomState(0).rand(2, 2, 6, 6).astype("float32")
        (ref,) = exe.run(main, feed={"sc_img": x}, fetch_list=[out],
                         scope=scope)
        from paddle_tpu.transpiler import apply_pass

        apply_pass(main, "bn_fold_pass", scope=scope)
        types = [op.type for op in main.global_block().ops]
        assert "batch_norm" not in types and "scale" not in types, types
        (got,) = exe.run(main, feed={"sc_img": x}, fetch_list=[out],
                         scope=scope)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_bn_fold_refuses_double_bias_chain():
    """fc-with-Bias -> elementwise_add(second bias) -> BN: folding only
    the add's operand would leave the fc's own bias unscaled — the pass
    must refuse, and the unfused program must still match itself."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x_in = layers.data("db_x", shape=[6])
        h = layers.fc(x_in, size=5, act=None)  # fc carries its own Bias
        b2 = layers.create_parameter([5], "float32", name="db_b2")
        out = layers.batch_norm(layers.elementwise_add(h, b2),
                                is_test=True)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = _startup_run(startup, scope)
        x = np.random.RandomState(0).rand(3, 6).astype("float32")
        from paddle_tpu.transpiler import apply_pass

        # normalize mul+add to a real fc op carrying the Bias slot —
        # the double-bias shape the fold must refuse
        apply_pass(main, "fc_fuse_pass")
        fc_ops = [op for op in main.global_block().ops
                  if op.type == "fc"]
        assert fc_ops and fc_ops[0].inputs.get("Bias")
        (ref,) = exe.run(main, feed={"db_x": x}, fetch_list=[out],
                         scope=scope)
        apply_pass(main, "bn_fold_pass", scope=scope)
        assert "batch_norm" in [op.type
                                for op in main.global_block().ops]
        (got,) = exe.run(main, feed={"db_x": x}, fetch_list=[out],
                         scope=scope)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_bn_fold_refuses_train_mode_bn():
    """A TRAIN-mode BN normalizes by batch statistics; folding the
    moving stats into the weights would silently change the math — the
    pass must leave it alone (clone(for_test=True) is the opt-in)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        img = layers.data("tm_img", shape=[3, 8, 8])
        c = layers.conv2d(img, num_filters=4, filter_size=3, act=None)
        layers.batch_norm(c, is_test=False)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        _startup_run(startup, scope)
        from paddle_tpu.transpiler import apply_pass

        apply_pass(main, "bn_fold_pass", scope=scope)
    assert "batch_norm" in [op.type for op in main.global_block().ops]


def test_bn_fold_respects_protected_mid_chain_fetch():
    """A protected fetch of the conv output must survive: the fold
    rewires the conv to write the BN output name, which would delete
    the fetched definition — refuse instead."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        img = layers.data("pf_img", shape=[3, 8, 8])
        c = layers.conv2d(img, num_filters=4, filter_size=3, act=None,
                          bias_attr=False)
        bn = layers.batch_norm(c, is_test=True)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = _startup_run(startup, scope)
        x = np.random.RandomState(0).rand(2, 3, 8, 8).astype("float32")
        main._protected_fetch_names = (c.name,)
        from paddle_tpu.transpiler import apply_pass

        apply_pass(main, "bn_fold_pass", scope=scope)
        assert "batch_norm" in [op.type for op in main.global_block().ops]
        # both fetches still evaluable
        exe.run(main, feed={"pf_img": x}, fetch_list=[c, bn], scope=scope)


def test_train_prune_pass_drops_loss_head_fetch_equal():
    """A train program pruned at the prediction cut loses its label
    slot, loss head and optimizer ops; the kept fetch is
    value-identical."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x_in = layers.data("tp_x", shape=[4])
        lbl = layers.data("tp_y", shape=[1], dtype="int64")
        h = layers.fc(x_in, size=8, act="relu")
        h = layers.dropout(h, 0.3)
        pred = layers.fc(h, size=3, act="softmax")
        loss = layers.mean(layers.cross_entropy(pred, lbl))
        fluid.optimizer.SGD(0.1).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = _startup_run(startup, scope)
        x = np.random.RandomState(0).rand(5, 4).astype("float32")
        infer = main.clone(for_test=True)
        (ref,) = exe.run(infer, feed={"tp_x": x}, fetch_list=[pred],
                         scope=scope)
        opt = fluid.InferenceTranspiler().transpile(
            main.clone(for_test=True), fluid.CPUPlace(), scope=scope,
            fetches=[pred])
        types = [op.type for op in opt.global_block().ops]
        assert "cross_entropy" not in types, types
        assert "dropout" not in types, types
        assert not any(t.endswith("_grad") or t == "sgd" for t in types), types
        # the label slot is below the cut: the pruned program must not
        # read it at all
        reads = {n for op in opt.global_block().ops
                 for n in op.input_arg_names()}
        assert "tp_y" not in reads
        (got,) = exe.run(opt, feed={"tp_x": x}, fetch_list=[pred],
                         scope=scope)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=0)


def test_weight_int8_pass_generic_program_parity():
    """weight_int8_pass quantizes ANY program's weights (here a plain
    fc MLP, not the serving engine): converted ops counted, outputs
    within the established post-training-quant tolerance."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x_in = layers.data("q8_x", shape=[16])
        h = layers.fc(x_in, size=32, act="relu")
        pred = layers.fc(h, size=8)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = _startup_run(startup, scope)
        x = np.random.RandomState(0).rand(4, 16).astype("float32")
        (ref,) = exe.run(main, feed={"q8_x": x}, fetch_list=[pred],
                         scope=scope)
        from paddle_tpu.contrib.quantize import quantize_weights_int8

        n = quantize_weights_int8(main, scope=scope, min_elems=64)
        assert n >= 2, n
        types = [op.type for op in main.global_block().ops]
        assert any(t.startswith("quantized_") for t in types), types
        (got,) = exe.run(main, feed={"q8_x": x}, fetch_list=[pred],
                         scope=scope)
    ref, got = np.asarray(ref), np.asarray(got)
    # int8 weight-only tolerance (tests/test_quant_int8.py discipline)
    assert np.max(np.abs(got - ref)) < 0.1 * (np.max(np.abs(ref)) + 1)


def test_inference_transpile_pipeline_end_to_end():
    """transpile(fetches=..., quantize_int8=True) runs fold -> prune ->
    int8 in one call on a conv+BN+relu+fc classifier."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        img = layers.data("p_img", shape=[3, 8, 8])
        lbl = layers.data("p_lbl", shape=[1], dtype="int64")
        c = layers.conv2d(img, num_filters=4, filter_size=3, act=None)
        bn = layers.batch_norm(c, is_test=True)
        flat = layers.flatten(layers.relu(bn), axis=1)
        pred = layers.fc(layers.dropout(flat, 0.3), size=10, act="softmax")
        loss = layers.mean(layers.cross_entropy(pred, lbl))
        fluid.optimizer.SGD(0.1).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = _startup_run(startup, scope)
        x = np.random.RandomState(0).rand(2, 3, 8, 8).astype("float32")
        (ref,) = exe.run(main.clone(for_test=True), feed={"p_img": x},
                         fetch_list=[pred], scope=scope)
        opt = fluid.InferenceTranspiler().transpile(
            main.clone(for_test=True), fluid.CPUPlace(), scope=scope,
            fetches=[pred], quantize_int8=True, int8_min_elems=64)
        types = [op.type for op in opt.global_block().ops]
        assert "batch_norm" not in types
        assert "cross_entropy" not in types
        assert any(t.startswith("quantized_") for t in types), types
        (got,) = exe.run(opt, feed={"p_img": x}, fetch_list=[pred],
                         scope=scope)
    ref, got = np.asarray(ref), np.asarray(got)
    assert np.max(np.abs(got - ref)) < 0.05, np.max(np.abs(got - ref))


# ---------------------------------------------------------------------------
# memory_optimize aliasing contracts
# ---------------------------------------------------------------------------
def test_memory_optimize_refuses_cross_dtype_and_shape():
    """The seed-era pool matched on numel/bytes only; aliasing is only
    sound between identically-typed, identically-shaped slots."""
    from paddle_tpu.transpiler import memory_optimize

    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x_in = layers.data("mo_x", shape=[4, 8], append_batch_size=False)
        a = layers.relu(x_in)           # f32 [4, 8], dies early
        b = layers.cast(a, "int64")     # int64 [4, 8]: HALF the numel of
        #                                 a same-bytes f32 — never alias a
        c = layers.reshape(layers.relu(x_in), shape=[32])  # f32 [32]
        d = layers.scale(layers.cast(b, "float32"), 2.0)
        out = layers.elementwise_add(
            layers.reshape(d, shape=[32]), c)
        layers.reduce_sum(out)
    plan = memory_optimize(main)
    block = main.global_block()
    for name, cand in plan["reuse"].items():
        v, cv = block.var(name), block.var(cand)
        assert str(v.dtype) == str(cv.dtype), (name, cand)
        assert tuple(v.shape) == tuple(cv.shape), (name, cand)


def test_memory_optimize_nested_block_liveness():
    """A var read ONLY inside a later op's sub-block (recompute here)
    must stay live until that op: the plan may not hand its storage to
    a var defined in between."""
    from paddle_tpu.transpiler import memory_optimize
    from paddle_tpu.transpiler.memory_optimization_transpiler import (
        ControlFlowGraph,
    )

    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        x_in = layers.data("nb_x", shape=[4, 8], append_batch_size=False)
        early = layers.relu(x_in)  # read only inside the sub-block below
        mid = layers.tanh(layers.scale(x_in, 2.0))

        def body(m):
            return layers.elementwise_add(m, early)

        out = layers.recompute(body, mid)
        layers.reduce_sum(out)

    cfg = ControlFlowGraph(main)
    ranges = cfg.live_ranges()
    rec_idx = next(i for i, op in enumerate(main.global_block().ops)
                   if op.type == "recompute")
    # the nested read extends early's live range to the recompute op
    assert ranges[early.name][1] >= rec_idx, ranges[early.name]
    plan = memory_optimize(main)
    assert plan["reuse"].get(mid.name) != early.name


# ---------------------------------------------------------------------------
# program autotuner
# ---------------------------------------------------------------------------
def _mini_program(hidden=8):
    from paddle_tpu import unique_name

    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup), \
            unique_name.guard():
        x_in = layers.data("at_x", shape=[4])
        h = layers.fc(x_in, size=hidden, act="relu")
        layers.fc(h, size=2)
    return main, startup


def test_autotune_search_cache_and_consult_only(tmp_path):
    from paddle_tpu.transpiler import autotune as at

    path = str(tmp_path / "ptc.json")
    main, _ = _mini_program()
    spec = {"at_x": ((4, 4), "float32")}

    # injected measurer: window 8 is the planted optimum; the greedy
    # search must find it and persist the decision
    def measure(decision):
        sps = 100.0
        if decision.get("steps_per_dispatch", 1) > 1:
            sps += 25.0
        if decision.get("bf16_amp"):
            sps -= 40.0  # the CPU reality: AMP must be rejected
        return sps

    at.clear_cache(forget_path=True)
    saved = {k: flags.get_flag(k)
             for k in ("program_tune_cache", "program_autotune")}
    flags.set_flags({"program_tune_cache": path, "program_autotune": 1})
    try:
        d = at.tune(main, spec, measure=measure)
        assert d["steps_per_dispatch"] == 8
        assert d["bf16_amp"] is False
        # the generator is no knob: it follows the place the step runs
        # on (Executor._rng_impl), so no decision names it and the
        # tuner has no flag to set for it
        assert "prng_impl" not in d and "prng_impl" not in at._KNOB_ORDER
        assert at.tuned_flags(d) == {}
        # hit path: no measurer needed
        d2 = at.tune(main, spec)
        assert d2 == d
        st = at.cache_stats()
        assert st["searched"] == 1 and st["stats"]["hits"] == 1

        # fresh-process view reloads the persisted decision
        at.clear_cache(forget_path=True)
        d3 = at.tune(main, spec)
        assert d3 == d

        # a DIFFERENT program signature in consult-only mode seeds the
        # all-defaults decision and never searches
        at.clear_cache(forget_path=True)
        flags.set_flags({"program_autotune": 0})
        other, other_st = _mini_program(hidden=16)  # distinct signature
        spec2 = {"at_x": ((4, 4), "float32")}
        d4 = at.tune(other, spec2, startup=other_st, fetches=[])
        assert d4 == at.DEFAULT_DECISION
        assert at.cache_stats()["stats"]["searches"] == 0
        # and the consult-only miss never lands on disk
        at.clear_cache(forget_path=True)
        flags.set_flags({"program_autotune": 1})
        d5 = at.tune(other, spec2)  # no measurer, no startup: defaults
        assert d5 == at.DEFAULT_DECISION
    finally:
        flags.set_flags(saved)
        at.clear_cache(forget_path=True)


def test_ci_pinned_program_tune_cache_consults_without_search():
    """The ci.sh transpiler lane pins FLAGS_program_tune_cache to the
    committed tests/data/ci_program_tune_cache.json with
    FLAGS_program_autotune=0: CI NEVER searches — the pinned decision
    for the reference mini program comes back verbatim, and a miss on
    any other signature seeds the all-defaults decision."""
    from paddle_tpu.transpiler import autotune as at

    if not str(flags.get_flag("program_tune_cache")).endswith(
            "ci_program_tune_cache.json"):
        pytest.skip("pinned program tune cache not configured "
                    "(the ci.sh transpiler lane sets it)")
    at.clear_cache(forget_path=True)
    try:
        main, _ = _mini_program()
        d = at.tune(main, {"at_x": ((4, 4), "float32")})
        # the committed searched decision (see tests/data/README note)
        assert d["steps_per_dispatch"] == 8, d
        assert set(d) == set(at.DEFAULT_DECISION), d  # no stale knob
        st = at.cache_stats()
        assert st["stats"]["searches"] == 0
        assert st["stats"]["hits"] == 1
        # unknown signature: all-defaults, still no search
        other, _ = _mini_program(hidden=32)
        d2 = at.tune(other, {"at_x": ((4, 4), "float32")})
        assert d2 == at.DEFAULT_DECISION
        assert at.cache_stats()["stats"]["searches"] == 0
    finally:
        at.clear_cache(forget_path=True)


def test_autotune_key_buckets_leading_dims_and_names_the_device():
    """The decision key: leading (row/batch) dims bucket to the next
    power of two, the last dim stays exact, operands join in order; a
    CPU run's device kind is its own universe, so a CI cache can never
    land on a chip."""
    from paddle_tpu.transpiler import autotune as at

    assert at._shape_bucket([(100, 768)]) == "128x768"
    assert at._shape_bucket([(128, 768)]) == "128x768"
    assert at._shape_bucket([(3, 5, 96)]) == "4x8x96"
    assert at._shape_bucket([(7,)]) == "7"
    assert at._shape_bucket([(100, 64), (64, 50)]) == "128x64,64x50"
    assert at._device_kind().startswith("interpret-")
    main, _ = _mini_program()
    assert (at._key(main, {"at_x": ((100, 4), "float32")})
            == at._key(main, {"at_x": ((128, 4), "float32")}))
    assert (at._key(main, {"at_x": ((100, 4), "float32")})
            != at._key(main, {"at_x": ((129, 4), "float32")}))


def test_autotune_signature_stable_and_value_insensitive():
    from paddle_tpu.transpiler.autotune import program_signature

    a, _ = _mini_program()
    b, _ = _mini_program()
    assert program_signature(a) == program_signature(b)
    c, _ = _mini_program(hidden=16)  # structurally different program
    assert program_signature(a) != program_signature(c)


@pytest.mark.slow
def test_autotuned_window_matches_per_step_trajectory():
    """steps_per_dispatch is schedule, not math: run_loop(K) reproduces
    K sequential run() losses exactly (same RNG fold indices), so a
    tuned window never changes the training trajectory.  Rides the
    ci.sh transpiler lane (-m \"\")."""
    from paddle_tpu.models import transformer as tfm

    main, st, _, fetches = _build_tfm()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        st.random_seed = 11
        exe.run(st)
        batch = tfm.make_fake_batch(2, SEQ, SEQ, _tiny_hp(), seed=1)
        per_step = []
        for _ in range(3):
            out = exe.run(main, feed=batch, fetch_list=fetches)
            per_step.append(float(np.asarray(out[0])))
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2 = fluid.Executor(fluid.CPUPlace())
        st.random_seed = 11
        exe2.run(st)
        batch = tfm.make_fake_batch(2, SEQ, SEQ, _tiny_hp(), seed=1)
        out = exe2.run_loop(3, main, feed=batch, fetch_list=fetches)
        assert float(np.asarray(out[0])) == per_step[-1]


# ---------------------------------------------------------------------------
# decode/serving epilogue satellite
# ---------------------------------------------------------------------------
def test_decode_and_ragged_builders_get_epilogue_fusions():
    """PR 11's 'epilogue passes rewrite training programs only' limit is
    closed: the classic decode step AND the continuous-batching ragged
    step carry fused fc / residual-LN ops (tests/test_serving.py's
    pooled == solo churn tests guard the numerics)."""
    from paddle_tpu.models import gpt2

    class HP(gpt2.GPT2Config):
        vocab_size = 97
        n_ctx = 32
        d_model = 16
        n_layer = 2
        n_head = 2
        dropout = 0.0

    with fluid.scope_guard(fluid.Scope()):
        main, _, _, _, _ = gpt2.gpt2_decode_step_program(HP, batch=2,
                                                         t_max=16)
    assert getattr(main, "_fc_fused_count", 0) >= 1
    assert getattr(main, "_residual_ln_fused_count", 0) >= 1
    types = [op.type for op in main.global_block().ops]
    assert "fc" in types and "fused_residual_ln" in types

    with fluid.scope_guard(fluid.Scope()):
        ragged, _, _, _, _ = gpt2.gpt2_ragged_step_program(
            HP, batch=2, t_max=16, width=4)
    assert getattr(ragged, "_fc_fused_count", 0) >= 1
    assert getattr(ragged, "_residual_ln_fused_count", 0) >= 1
