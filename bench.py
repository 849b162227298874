#!/usr/bin/env python
"""Benchmark driver: ResNet-50 training throughput (images/sec/chip).

Mirrors the reference's benchmark harness role
(benchmark/fluid/fluid_benchmark.py + models/resnet.py) on one TPU chip.
Baseline anchor: the reference's best published ResNet-50 training number,
82.35 images/sec (MKL-DNN, Xeon 6148 — benchmark/IntelOptimizedPaddle.md:39,
see BASELINE.md; no GPU number is published in-tree).

Prints ONE json line: {"metric", "value", "unit", "vs_baseline"}.

Process shape: the parent NEVER imports jax (a parent that touched jax
would hold the chip and starve the child); it runs the measurement in
ONE group-killable child.  A host with no TPU, a leg that raises, or a
child that times out all end in a non-zero exit with no metric printed:
a number under a device metric name comes from the device or not at all.
"""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_IMAGES_PER_SEC = 82.35  # reference ResNet-50 train, bs128 (BASELINE.md)


def _bench_impl():
    import numpy as np
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models.resnet import build_resnet_train_program

    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        sys.stderr.write(
            "bench: no TPU (jax sees %s) — refusing to measure\n"
            % (jax.devices(),))
        sys.exit(3)
    on_tpu = True
    # BENCH_PALLAS=0 disables the hand-kernel layer (default ON on the
    # chip: the matmul-epilogue/xent/flash kernels ARE the MFU story);
    # BENCH_TUNE_CACHE points FLAGS_kernel_tune_cache at a persisted
    # block-size cache so repeat captures skip the block search
    _pallas_bench_env(on_tpu)
    batch_size = int(os.environ.get("BENCH_BATCH", 128 if on_tpu else 8))
    image_hw = int(os.environ.get("BENCH_IMAGE", 224 if on_tpu else 64))
    steps = max(1, int(os.environ.get("BENCH_STEPS", 20 if on_tpu else 3)))
    warmup = max(1, int(os.environ.get("BENCH_WARMUP", 3 if on_tpu else 1)))

    # BENCH_ONLY=transformer: diagnostic mode — skip the ResNet leg and
    # emit just the transformer result (not a driver-format headline)
    if os.environ.get("BENCH_ONLY") == "transformer":
        out = {"metric": "transformer_only_diag"}
        out["transformer"] = _transformer_bench(
            on_tpu, fluid.TPUPlace(0).jax_device())
        print(json.dumps(out))
        return

    use_bf16 = os.environ.get("BENCH_BF16", "1" if on_tpu else "0") == "1"
    # BENCH_READER=1 measures the --use_reader_op path (in-program
    # py_reader, H2D overlapped).  Default is the once-staged device
    # batch: the leg times the training step, not the 77MB/step upload
    # (the reader path is correctness-covered in
    # tests/test_pipeline_and_metrics.py).
    use_reader = os.environ.get("BENCH_READER", "0") == "1"
    # BENCH_LAYOUT=NHWC runs the conv trunk channels-last via the
    # nhwc_layout_pass (transposes only at trunk boundaries)
    use_nhwc = os.environ.get("BENCH_LAYOUT", "NCHW").upper() == "NHWC"
    place = fluid.TPUPlace(0)
    device = place.jax_device()

    rng = np.random.RandomState(0)
    x = rng.rand(batch_size, 3, image_hw, image_hw).astype("float32")
    y = rng.randint(0, 1000, (batch_size, 1)).astype("int64")

    if use_reader:
        main_prog, startup, feeds, fetches, reader = build_resnet_train_program(
            image_shape=(3, image_hw, image_hw), class_dim=1000, depth=50,
            lr=0.1, use_bf16=use_bf16, use_nhwc=use_nhwc, use_reader_op=True,
        )

        def batches():
            for _ in range(warmup + steps + 2):
                yield {reader.out_names[0]: x, reader.out_names[1]: y}

        reader.decorate_batch_generator(lambda: batches())
        exe = fluid.Executor(place)
        exe.run(startup)
        reader.start()
        feed = {}
    else:
        main_prog, startup, feeds, fetches = build_resnet_train_program(
            image_shape=(3, image_hw, image_hw), class_dim=1000, depth=50,
            lr=0.1, use_bf16=use_bf16, use_nhwc=use_nhwc,
        )
        exe = fluid.Executor(place)
        exe.run(startup)
        feed = {
            "image": jax.device_put(x, device),
            "label": jax.device_put(y, device),
        }

    for _ in range(warmup):
        out = exe.run(main_prog, feed=feed, fetch_list=fetches)
    np.asarray(out[0])  # sync

    # BENCH_PROFILE=<dir>: capture a device trace (xplane) of the timed
    # steps for MFU attribution — TensorBoard/xprof readable
    profile_dir = os.environ.get("BENCH_PROFILE", "")
    if profile_dir:
        try:
            jax.profiler.start_trace(profile_dir)
        except (RuntimeError, OSError) as e:
            sys.stderr.write("BENCH_PROFILE disabled (%r)\n" % (e,))
            profile_dir = ""
    try:
        t0 = time.time()
        for _ in range(steps):
            out = exe.run(main_prog, feed=feed, fetch_list=fetches,
                          return_numpy=False)
        jax.block_until_ready(out)  # sync on the final step
        dt = time.time() - t0
    finally:
        if profile_dir:
            try:
                jax.profiler.stop_trace()
            except RuntimeError as e:
                sys.stderr.write("BENCH_PROFILE trace not written: %r\n" % e)
    if use_reader:
        reader.reset()

    ips = batch_size * steps / dt
    from paddle_tpu.utils import flops as flops_util

    device = place.jax_device()
    step_flops = flops_util.program_flops(main_prog, batch_hint=batch_size)
    mfu = flops_util.mfu(step_flops, steps, dt, device)

    result = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(ips / BASELINE_IMAGES_PER_SEC, 3),
        "model_tflops_per_step": round(step_flops / 1e12, 3),
    }
    if mfu is not None:
        result["mfu"] = round(mfu, 4)
    result["kernel_attribution"] = _kernel_attribution()

    # BENCH_INNER=K: also time K steps inside ONE compiled lax.scan
    # (Executor.run_loop) — separates device throughput from per-step
    # host dispatch; the delta vs the headline IS the dispatch tax
    inner = int(os.environ.get("BENCH_INNER", "0"))
    if inner > 0 and not use_reader:
        out = exe.run_loop(inner, main_prog, feed=feed,
                           fetch_list=fetches, return_numpy=False)
        jax.block_until_ready(out)  # compile + warm
        t0 = time.time()
        out = exe.run_loop(inner, main_prog, feed=feed,
                           fetch_list=fetches, return_numpy=False)
        jax.block_until_ready(out)
        dt_in = time.time() - t0
        ips_in = batch_size * inner / dt_in
        result["inner_loop"] = {
            "iters": inner,
            "images_per_sec": round(ips_in, 2),
            "dispatch_tax_pct": round(max(0.0, 1 - ips / ips_in) * 100, 1),
        }
        m_in = flops_util.mfu(step_flops, inner, dt_in, device)
        if m_in is not None:
            result["inner_loop"]["mfu"] = round(m_in, 4)

    # optional legs: (env knob, default, result key, fn).  A leg that
    # raises fails the whole run — an error string inside an rc-0 JSON
    # line reads as a result.
    for knob, default, key, fn in (
        ("BENCH_TRANSFORMER", "1", "transformer", _transformer_bench),
        ("BENCH_INFER", "0", "infer", _infer_bench),
        ("BENCH_DECODE", "0", "decode", _decode_bench),
        ("BENCH_SERVE", "0", "serve", _serve_bench),
        ("BENCH_SERVE_SPEC", "0", "serve_spec", _serve_spec_bench),
        ("BENCH_SERVE_PREFIX", "0", "serve_prefix", _serve_prefix_bench),
        ("BENCH_SERVE_TP", "0", "serve_tp", _serve_tp_bench),
        ("BENCH_SPMD_TRAIN", "0", "spmd_train", _spmd_train_bench),
        ("BENCH_SPMD_PP", "0", "spmd_pp", _spmd_pp_bench),
        ("BENCH_FABRIC", "0", "fabric", _fabric_bench),
    ):
        if os.environ.get(knob, default) == "1":
            result[key] = fn(on_tpu, device)
    # model-breadth diagnostics (fluid_benchmark.py model matrix): off by
    # default — the vgg/se_resnext shapes roughly double the run
    if os.environ.get("BENCH_MODELS", "0") == "1":
        result["models"] = {}
        for name in ("vgg16", "se_resnext50", "stacked_lstm", "bert_base",
                     "deepfm", "gpt2_345m"):
            result["models"][name] = _model_bench(name, on_tpu, device)
            # incremental record: a timeout-killed run must not lose
            # the models already measured
            sys.stderr.write("MODEL_RESULT %s %s\n" % (
                name, json.dumps(result["models"][name])))
    print(json.dumps(result))


def _pallas_bench_env(on_tpu):
    """Arm the Pallas kernel layer + tuning cache for this bench run.
    Returns whether the kernels are on.  Resets the trace-time
    attribution counters so each leg's snapshot is its own."""
    use_pallas = os.environ.get("BENCH_PALLAS",
                                "1" if on_tpu else "0") == "1"
    from paddle_tpu import flags as _flags
    from paddle_tpu.ops import kernel_tuning

    if use_pallas:
        updates = {"use_pallas": True}
        cache = os.environ.get("BENCH_TUNE_CACHE", "")
        if cache:
            updates["kernel_tune_cache"] = cache
        _flags.set_flags(updates)
    else:
        # force OFF: flags.py loads FLAGS_use_pallas from the process
        # env at import, so the no-kernel baseline of an A/B must not
        # inherit a stray FLAGS_use_pallas=1
        _flags.set_flags({"use_pallas": False})
    kernel_tuning.reset_attribution()
    return use_pallas


def _kernel_attribution():
    """Per-phase kernel attribution for the result JSON: pallas-hit
    counters per kernel family (attention / matmul-epilogue / xent /
    layernorm / recurrent) plus tuning-cache hit/miss/search-ms — the
    evidence that makes an MFU regression diagnosable ('attention
    stopped dispatching to flash' vs 'the tuning cache went cold').
    Counters tick at trace time, so they attribute the compiled step's
    contents, not per-run dispatch counts."""
    from paddle_tpu.ops import kernel_tuning

    return kernel_tuning.attribution()


def _time_program(exe, prog, feed, fetches, warmup, steps):
    import time as _t

    import jax
    import numpy as np

    for _ in range(warmup):
        out = exe.run(prog, feed=feed, fetch_list=fetches)
    np.asarray(out[0])
    t0 = _t.time()
    for _ in range(steps):
        out = exe.run(prog, feed=feed, fetch_list=fetches, return_numpy=False)
    jax.block_until_ready(out)
    return _t.time() - t0


def _model_bench(name, on_tpu, device):
    """One benchmark/fluid/models/* leg: images|examples/sec + MFU."""
    import numpy as np
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.utils import flops as flops_util

    steps = max(1, int(os.environ.get("BENCH_MODEL_STEPS", 10 if on_tpu else 2)))
    warmup = 2 if on_tpu else 1
    rng = np.random.RandomState(0)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.framework.program_guard(main, startup):
        if name in ("vgg16", "se_resnext50"):
            bs = int(os.environ.get("BENCH_MODEL_BATCH", 32 if on_tpu else 2))
            hw = 224 if on_tpu else 32
            img = layers.data("image", shape=[3, hw, hw])
            label = layers.data("label", shape=[1], dtype="int64")
            if name == "vgg16":
                from paddle_tpu.models.vgg import vgg16

                pred = vgg16(img, class_dim=1000 if on_tpu else 10)
            else:
                from paddle_tpu.models.se_resnext import se_resnext

                pred = se_resnext(img, class_dim=1000 if on_tpu else 10,
                                  depth=50)
            loss = layers.mean(layers.cross_entropy(pred, label))
            fluid.optimizer.Momentum(0.01, momentum=0.9).minimize(loss)
            feed_np = {
                "image": rng.rand(bs, 3, hw, hw).astype("float32"),
                "label": rng.randint(0, 10, (bs, 1)).astype("int64"),
            }
            unit, per_step = "images/sec", bs
        elif name == "bert_base":
            # BASELINE config 3: BERT-base pretraining, fused attention
            from paddle_tpu.models import bert

            bs = int(os.environ.get("BENCH_MODEL_BATCH", 32 if on_tpu else 2))
            seq = 128 if on_tpu else 16

            class HP(bert.BertConfig):
                fused_attn = True
                n_layer = bert.BertConfig.n_layer if on_tpu else 2
                vocab_size = bert.BertConfig.vocab_size if on_tpu else 500

            main_b, startup_b, _feeds, fetches_b = bert.bert_pretrain_program(
                HP, seq_len=seq, use_bf16=on_tpu)
            feed_np = bert.make_fake_bert_batch(bs, seq, HP, seed=0)
            unit, per_step = "examples/sec", bs
            main, startup, loss = main_b, startup_b, fetches_b[0]
        elif name == "gpt2_345m":
            # BASELINE config 5: GPT-2 345M causal-LM train, single chip
            # (the TP+DP step is measured separately on the virtual mesh
            # by the gpt2_tp dist leg — one real chip here)
            from paddle_tpu.models import gpt2

            bs = int(os.environ.get("BENCH_MODEL_BATCH", 8 if on_tpu else 2))
            seq = int(os.environ.get("BENCH_GPT2_SEQ", 512 if on_tpu else 16))

            class HP(gpt2.GPT2Config):
                # the 345M shape (gpt2-medium): d_model=1024 x 24 layers
                d_model = 1024 if on_tpu else 64
                n_layer = 24 if on_tpu else 2
                n_head = 16 if on_tpu else 2
                n_ctx = max(1024, seq)
                vocab_size = 50257 if on_tpu else 500

            main_g, startup_g, _feeds, fetches_g = gpt2.gpt2_lm_program(
                HP, seq_len=seq, use_bf16=on_tpu)
            feed_np = gpt2.make_fake_lm_batch(bs, seq, HP, seed=0)
            unit, per_step = "examples/sec", bs
            main, startup, loss = main_g, startup_g, fetches_g[0]
        elif name == "deepfm":
            # BASELINE config 4: DeepFM CTR, sparse embeddings
            from paddle_tpu.models.ctr_deepfm import build_deepfm_train

            bs = int(os.environ.get("BENCH_MODEL_BATCH",
                                    4096 if on_tpu else 64))
            fields = [1000] * 26 if on_tpu else [50] * 4
            feeds, loss, _pred = build_deepfm_train(
                fields, dense_dim=13 if on_tpu else 4, embed_dim=16,
                is_sparse=True)
            fluid.optimizer.Adagrad(0.01).minimize(loss)
            feed_np = {}
            for i, dim in enumerate(fields):
                feed_np["C%d" % i] = rng.randint(
                    0, dim, (bs, 1)).astype("int64")
            feed_np["dense"] = rng.rand(
                bs, 13 if on_tpu else 4).astype("float32")
            feed_np["click"] = rng.randint(0, 2, (bs, 1)).astype("float32")
            unit, per_step = "examples/sec", bs
        else:
            from paddle_tpu.models.stacked_dynamic_lstm import (
                build_stacked_lstm_train,
            )

            bs = int(os.environ.get("BENCH_MODEL_BATCH", 32 if on_tpu else 4))
            seq = 64 if on_tpu else 16
            # lstm_size=512 matches the reference benchmark config
            # (benchmark/fluid/models/stacked_dynamic_lstm.py:94) and makes
            # the fused VMEM-resident LSTM kernel lane-eligible
            feeds, loss, _acc = build_stacked_lstm_train(
                dict_size=10000 if on_tpu else 500, seq_len_max=seq,
                emb_dim=512 if on_tpu else 64,
                hidden_dim=512 if on_tpu else 64)
            fluid.optimizer.Adam(0.001).minimize(loss)
            feed_np = {
                "words": rng.randint(0, 500, (bs, seq)).astype("int64"),
                "seq_len": np.full((bs,), seq, "int64"),
                "label": rng.randint(0, 2, (bs, 1)).astype("int64"),
            }
            unit, per_step = "examples/sec", bs
    import jax as _jax

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace())
        exe.run(startup)
        feed = {k: _jax.device_put(v, device) for k, v in feed_np.items()}
        dt = _time_program(exe, main, feed, [loss], warmup, steps)
    out = {
        "value": round(per_step * steps / dt, 2),
        "unit": unit,
    }
    step_flops = flops_util.program_flops(main, batch_hint=bs)
    mfu = flops_util.mfu(step_flops, steps, dt, device)
    if mfu is not None:
        out["mfu"] = round(mfu, 4)
    return out


def _infer_bench(on_tpu, device):
    """ResNet-50 INFERENCE throughput at the reference's bs16 config
    (IntelOptimizedPaddle.md: 217.69 img/s best published) in three
    regimes: f32, bf16 (AMP rewrite), int8 (QAT-transpiled -> frozen ->
    convert_to_int8; dynamic abs-max activation scales so no training is
    needed — throughput, not accuracy, is measured)."""
    import numpy as np
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models.resnet import resnet_imagenet
    from paddle_tpu.contrib.quantize import QuantizeTranspiler

    bs = int(os.environ.get("BENCH_INFER_BATCH", 16 if on_tpu else 2))
    hw = 224 if on_tpu else 64
    steps = int(os.environ.get("BENCH_INFER_STEPS", 30 if on_tpu else 2))
    warmup = 3 if on_tpu else 1
    rng = np.random.RandomState(0)
    x = rng.rand(bs, 3, hw, hw).astype("float32")
    out = {}

    def leg(regime):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.framework.program_guard(main, startup):
            img = layers.data("image", shape=[3, hw, hw])
            pred = resnet_imagenet(img, class_dim=1000, depth=50,
                                   is_test=regime != "int8")
            if regime == "int8":
                qt = QuantizeTranspiler(activation_quantize_type="abs_max")
                qt.training_transpile(main, startup)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(
                fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace())
            exe.run(startup)
            prog = main.clone(for_test=True)._prune(pred.name)
            if regime == "int8":
                qt.freeze_program(prog, scope=scope)
                n = qt.convert_to_int8(prog, scope=scope)
                if not n:
                    raise RuntimeError("no ops converted to int8")
            elif regime == "bf16":
                from paddle_tpu.contrib.mixed_precision import rewrite_bf16

                rewrite_bf16(prog)
            feed = {"image": jax.device_put(x, device)}
            dt = _time_program(exe, prog, feed, [pred.name], warmup, steps)
        return {"value": round(bs * steps / dt, 2),
                "unit": "images/sec"}

    for regime in ("f32", "bf16", "int8"):
        try:
            out[regime] = leg(regime)
        except Exception as e:
            sys.stderr.write("infer %s leg failed: %r\n" % (regime, e))
            out[regime] = {"error": repr(e)[:200]}
    out["batch_size"] = bs
    return out


def _decode_bench(on_tpu, device):
    """Generation throughput: KV-cached incremental decode vs the full
    re-encode path on a small GPT-2 (tokens/sec of NEW tokens)."""
    import time as _t

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import gpt2

    class HP(gpt2.GPT2Config):
        vocab_size = 8000 if on_tpu else 200
        n_ctx = 256 if on_tpu else 64
        d_model = 256 if on_tpu else 64
        n_layer = 4 if on_tpu else 2
        n_head = 4 if on_tpu else 2
        # BENCH_DECODE_KV=k: grouped-query attention with k kv heads —
        # the KV cache (decode's HBM traffic) shrinks n_head/k-fold
        n_kv_head = int(os.environ.get("BENCH_DECODE_KV", "0")) or None
        dropout = 0.0

    B = int(os.environ.get("BENCH_DECODE_BATCH", 8 if on_tpu else 2))
    T = HP.n_ctx
    new = int(os.environ.get("BENCH_DECODE_TOKENS", T // 2))
    scope = fluid.Scope()
    out = {}
    with fluid.scope_guard(scope):
        full_main, full_startup, _, full_fetch = gpt2.gpt2_logits_program(
            HP, seq_len=T)
        step_main, cache_startup, _, step_fetch, _ = \
            gpt2.gpt2_decode_step_program(HP, batch=B, t_max=T)
        exe = fluid.Executor(fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace())
        full_startup.random_seed = 23  # shared with the self-draft copy
        exe.run(full_startup)
        prompt = np.random.RandomState(0).randint(
            1, HP.vocab_size, (B, 4)).astype("int64")
        for name, fn in (
            ("full_reencode", lambda: gpt2.greedy_generate(
                exe, full_main, full_fetch, prompt, new)),
            ("kv_cached", lambda: gpt2.greedy_generate_cached(
                exe, step_main, cache_startup, step_fetch, prompt, new)),
        ):
            fn()  # warm compile
            t0 = _t.time()
            fn()
            dt = _t.time() - t0
            out[name] = {"value": round(B * new / dt, 1),
                         "unit": "new tokens/sec"}
            sys.stderr.write("DECODE_RESULT %s %s\n" % (
                name, json.dumps(out[name])))

        # prefill-dominated workload: long prompt, few new tokens — the
        # W-wide chunked prefill collapses P dispatches into ceil(P/W)
        # MXU-shaped ones (value = processed prompt+new tokens/sec)
        Wp = int(os.environ.get("BENCH_DECODE_PREFILL_W",
                                32 if on_tpu else 8))
        long_prompt = np.random.RandomState(1).randint(
            1, HP.vocab_size, (B, T // 2)).astype("int64")
        new2 = max(4, T // 8)
        wide_main, _, _, wide_fetch, _ = gpt2.gpt2_decode_step_program(
            HP, batch=B, t_max=T, width=Wp)
        for name, pf in (
            ("long_prompt_onetoken_prefill", None),
            ("long_prompt_chunked_prefill", (wide_main, wide_fetch, Wp, T)),
        ):
            gpt2.greedy_generate_cached(
                exe, step_main, cache_startup, step_fetch, long_prompt,
                new2, prefill=pf)  # warm compile
            t0 = _t.time()
            gpt2.greedy_generate_cached(
                exe, step_main, cache_startup, step_fetch, long_prompt,
                new2, prefill=pf)
            dt = _t.time() - t0
            out[name] = {
                "value": round(B * (T // 2 + new2) / dt, 1),
                "unit": "prompt+new tokens/sec",
                "prefill_width": Wp if pf else 1,
            }
            sys.stderr.write("DECODE_RESULT %s %s\n" % (
                name, json.dumps(out[name])))

        # speculative decode CEILING: a self-copy draft accepts every
        # proposal (same weights), so this measures the best-case
        # tokens/sec when target dispatches amortize over k+1 tokens —
        # the realistic number interpolates toward kv_cached with the
        # real draft's acceptance rate
        K = max(2, int(os.environ.get("BENCH_DECODE_SPEC_K", "4")))
        spec_wide, _, _, spec_wide_fetch, _ = gpt2.gpt2_decode_step_program(
            HP, batch=B, t_max=T, width=K)
        copy_scope = fluid.Scope()
        with fluid.scope_guard(copy_scope):
            _, c_startup, _, _ = gpt2.gpt2_logits_program(HP, seq_len=T)
            c_step, c_cache_startup, _, c_step_fetch, _ = \
                gpt2.gpt2_decode_step_program(HP, batch=B, t_max=T)
        c_startup.random_seed = full_startup.random_seed
        fluid.Executor(
            fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace()
        ).run(c_startup, scope=copy_scope)

        def spec():
            return gpt2.speculative_generate_cached(
                exe, step_main, cache_startup, step_fetch,
                spec_wide, spec_wide_fetch, K,
                c_step, c_cache_startup, c_step_fetch,
                prompt, new, draft_scope=copy_scope)

        spec()  # warm compile
        t0 = _t.time()
        _, stats = spec()
        dt = _t.time() - t0
        out["speculative_selfdraft"] = {
            "value": round(B * new / dt, 1),
            "unit": "new tokens/sec",
            "spec_k": K,
            "accept_rate": round(stats["accept_rate"], 3),
            "target_dispatches": stats["rounds"],
        }
    return out


def _serve_bench(on_tpu, device):
    """Continuous-batching serving leg (BENCH_SERVE=1): a seeded Poisson
    arrival trace over mixed prompt/output lengths through the
    slot-pool engine, A/B'd against serve-one-at-a-time on the SAME
    trace (same compiled pooled program, occupancy 1).  Reports
    sustained new tokens/s, p50/p99 per-request latency (arrivals map
    to wall time via the engine's measured mean step seconds for both
    systems), slot-occupancy %, and the engine's COUNTERS-style
    aggregates (steps, admit/prefill/decode splits, compile count —
    which must stay flat across the run: the no-retrace contract)."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import gpt2
    from paddle_tpu.serving import (
        ServingEngine,
        make_poisson_trace,
        serve_one_at_a_time,
    )

    class HP(gpt2.GPT2Config):
        vocab_size = 8000 if on_tpu else 200
        n_ctx = 256 if on_tpu else 64
        d_model = 256 if on_tpu else 64
        n_layer = 4 if on_tpu else 2
        n_head = 4 if on_tpu else 2
        dropout = 0.0

    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8 if on_tpu else 4))
    width = int(os.environ.get("BENCH_SERVE_WIDTH", 16 if on_tpu else 8))
    n_req = int(os.environ.get("BENCH_SERVE_REQS", 32 if on_tpu else 16))
    rate = float(os.environ.get("BENCH_SERVE_RATE", "2.0"))
    t_max = HP.n_ctx
    trace = make_poisson_trace(
        n_req, rate,
        prompt_len_range=(4, t_max // 4),
        out_len_range=(4, t_max // 4),
        vocab_size=HP.vocab_size,
        seed=int(os.environ.get("BENCH_SERVE_SEED", "0")),
        sampled_fraction=0.5)

    scope = fluid.Scope()
    out = {}
    with fluid.scope_guard(scope):
        _, lm_startup, _, _ = gpt2.gpt2_logits_program(HP, seq_len=t_max)
        exe = fluid.Executor(fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace())
        lm_startup.random_seed = 23
        exe.run(lm_startup)
        eng = ServingEngine(exe, HP, n_slots=slots, width=width,
                            t_max=t_max)
        eng.run(trace[:2])  # warm compile (step + reset + startup)
        compiles_warm = exe.compile_count
        results, stats = eng.run(trace)
        lat = sorted(r["latency_s"] for r in results.values())

        def pct(sorted_vals, p):
            return sorted_vals[min(len(sorted_vals) - 1,
                                   int(p * len(sorted_vals)))]

        out["continuous_batching"] = {
            "value": stats["tokens_per_s"],
            "unit": "new tokens/sec",
            "p50_latency_s": round(pct(lat, 0.50), 4),
            "p99_latency_s": round(pct(lat, 0.99), 4),
            "occupancy_pct": stats["occupancy_pct"],
            "slots": slots,
            "width": width,
            "requests": n_req,
            "steps": stats["steps"],
            "prefill_steps": stats["prefill_steps"],
            "decode_steps": stats["decode_steps"],
            "new_tokens": stats["new_tokens"],
            "retraces_during_run": exe.compile_count - compiles_warm,
        }
        sys.stderr.write("SERVE_RESULT continuous_batching %s\n"
                         % json.dumps(out["continuous_batching"]))

        base_results, base_stats = serve_one_at_a_time(
            eng, trace, arrival_step_seconds=stats["step_s_mean"])
        blat = sorted(r["latency_s"] for r in base_results.values())
        out["serve_one_at_a_time"] = {
            "value": base_stats["tokens_per_s"],
            "unit": "new tokens/sec",
            "p50_latency_s": round(pct(blat, 0.50), 4),
            "p99_latency_s": round(pct(blat, 0.99), 4),
        }
        sys.stderr.write("SERVE_RESULT serve_one_at_a_time %s\n"
                         % json.dumps(out["serve_one_at_a_time"]))
        base_tps = base_stats["tokens_per_s"] or 1.0
        out["speedup_vs_one_at_a_time"] = round(
            stats["tokens_per_s"] / base_tps, 2)
        # exactness spot-check rides the bench: the pooled run's token
        # streams must equal the solo baseline's, request for request
        mismatches = sum(
            0 if np.array_equal(results[r.rid]["tokens"],
                                base_results[r.rid]["tokens"]) else 1
            for r in trace)
        out["exactness_mismatches"] = mismatches
        sys.stderr.write("SERVE_RESULT speedup %s mismatches %d\n"
                         % (out["speedup_vs_one_at_a_time"], mismatches))
    return out


def _serve_spec_bench(on_tpu, device):
    """In-pool speculative decoding leg (BENCH_SERVE_SPEC=1): the SAME
    seeded Poisson trace through (a) the plain pooled engine and (b) a
    ServingEngine(draft=..., spec_k=K) — per round the draft proposes
    k-1 tokens and ONE widened target dispatch verifies anchor+drafts.
    Draft flavor via BENCH_SERVE_SPEC_DRAFT: "half" (default) truncates
    the target to n_layer//2 layers with the surviving weights copied
    by name into the draft's own scope (the separate-draft path);
    "self" re-hosts the target's weights over a second KV pool (the
    pool-worker failover mode — exact but compute-neutral).  Reports
    tok/s for both, the acceptance rate (aggregate + per-request p50),
    target-dispatch counts, and the always-on exactness checks: greedy
    pooled spec streams vs the plain engine AND vs the solo
    greedy_generate_cached chain; sampled pooled spec streams vs
    run_solo on the same spec engine (the keyed-resolver contract)."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import gpt2
    from paddle_tpu.serving import ServingEngine, make_poisson_trace

    class HP(gpt2.GPT2Config):
        vocab_size = 8000 if on_tpu else 200
        n_ctx = 256 if on_tpu else 64
        d_model = 256 if on_tpu else 64
        n_layer = 4 if on_tpu else 2
        n_head = 4 if on_tpu else 2
        dropout = 0.0

    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8 if on_tpu else 4))
    width = int(os.environ.get("BENCH_SERVE_WIDTH", 16 if on_tpu else 8))
    n_req = int(os.environ.get("BENCH_SERVE_REQS", 32 if on_tpu else 16))
    rate = float(os.environ.get("BENCH_SERVE_RATE", "2.0"))
    spec_k = int(os.environ.get("BENCH_SERVE_SPEC_K", "4"))
    flavor = os.environ.get("BENCH_SERVE_SPEC_DRAFT", "self")
    t_max = HP.n_ctx
    trace = make_poisson_trace(
        n_req, rate,
        prompt_len_range=(4, t_max // 4),
        out_len_range=(4, t_max // 4),
        vocab_size=HP.vocab_size,
        seed=int(os.environ.get("BENCH_SERVE_SEED", "0")),
        sampled_fraction=0.5)

    def pct(sorted_vals, p):
        return sorted_vals[min(len(sorted_vals) - 1,
                               int(p * len(sorted_vals)))]

    scope = fluid.Scope()
    out = {"spec_k": spec_k, "draft": flavor}
    with fluid.scope_guard(scope):
        _, lm_startup, _, _ = gpt2.gpt2_logits_program(HP, seq_len=t_max)
        exe = fluid.Executor(fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace())
        lm_startup.random_seed = 23
        exe.run(lm_startup)

        base = ServingEngine(exe, HP, n_slots=slots, width=width,
                             t_max=t_max)
        base.run(trace[:2])  # warm compile
        base_res, base_stats = base.run(trace)

        if flavor == "self":
            draft = "self"
        else:
            # truncated draft: first half of the target's blocks + the
            # shared embeddings/final-ln, weights copied by NAME into
            # the draft's own scope (same builder => same param names)
            class DraftHP(HP):
                n_layer = max(1, HP.n_layer // 2)

            draft_scope = fluid.Scope()
            with fluid.scope_guard(draft_scope):
                d_main, d_startup, _, _ = gpt2.gpt2_logits_program(
                    DraftHP, seq_len=t_max)
                d_startup.random_seed = 23
                exe.run(d_startup, scope=draft_scope)
            copied = 0
            for p in d_main.global_block().all_parameters():
                src = scope.find_var(p.name)
                if src is not None:
                    draft_scope.set(p.name, src)
                    copied += 1
            out["draft_params_copied"] = copied
            out["draft_layers"] = int(DraftHP.n_layer)
            draft = (DraftHP, draft_scope)

        eng = ServingEngine(exe, HP, n_slots=slots, width=width,
                            t_max=t_max, draft=draft, spec_k=spec_k)
        eng.run(trace[:2])  # warm compile (step + draft + spec resolve)
        compiles_warm = exe.compile_count
        results, stats = eng.run(trace)
        acc = sorted(r["accept_rate"] for r in results.values()
                     if r["spec_proposed"])
        out["speculative"] = {
            "value": stats["tokens_per_s"],
            "unit": "new tokens/sec",
            "accept_rate": round(stats["accept_rate"], 4),
            "accept_rate_p50": round(pct(acc, 0.50), 4) if acc else 1.0,
            "spec_rounds": stats["spec_rounds"],
            "spec_proposed": stats["spec_proposed"],
            "spec_accepted": stats["spec_accepted"],
            "draft_steps": stats["draft_steps"],
            "target_dispatches": stats["prefill_chunks"]
            + stats["spec_rounds"],
            "new_tokens": stats["new_tokens"],
            "retraces_during_run": exe.compile_count - compiles_warm,
        }
        out["plain"] = {
            "value": base_stats["tokens_per_s"],
            "unit": "new tokens/sec",
            "target_dispatches": base_stats["prefill_chunks"]
            + base_stats["decode_steps"],
            "new_tokens": base_stats["new_tokens"],
        }
        out["speedup_vs_plain"] = round(
            stats["tokens_per_s"] / (base_stats["tokens_per_s"] or 1.0), 2)
        # the number that transfers to a real (cheap-draft) deployment:
        # how many TARGET dispatches each emitted token costs
        out["target_dispatches_per_token"] = round(
            out["speculative"]["target_dispatches"]
            / max(1, stats["new_tokens"]), 3)
        out["target_dispatches_per_token_plain"] = round(
            out["plain"]["target_dispatches"]
            / max(1, base_stats["new_tokens"]), 3)

        # exactness rides the bench: greedy pooled spec == plain pooled
        # == solo cached chain; sampled pooled spec == its own run_solo
        mismatches = 0
        for r in trace:
            if r.greedy and not np.array_equal(
                    results[r.rid]["tokens"], base_res[r.rid]["tokens"]):
                mismatches += 1
        step_main, cst, _, sfetch, _ = gpt2.gpt2_decode_step_program(
            HP, batch=1, t_max=t_max)
        solo_budget = 4
        for r in trace:
            if solo_budget == 0:
                break
            if r.greedy:
                ref = gpt2.greedy_generate_cached(
                    exe, step_main, cst, sfetch, r.prompt[None, :],
                    r.max_new_tokens)[0, r.prompt.size:]
            else:
                ref, _ = eng.run_solo(r)
            got = np.asarray(results[r.rid]["tokens"])
            ref = np.asarray(ref)[:got.size]
            if not np.array_equal(got, ref):
                mismatches += 1
            solo_budget -= 1
        out["exactness_mismatches"] = mismatches
        sys.stderr.write(
            "SERVE_RESULT speculative %s\n" % json.dumps(out["speculative"]))
        sys.stderr.write(
            "SERVE_RESULT spec_speedup %s mismatches %d\n"
            % (out["speedup_vs_plain"], mismatches))
    return out


def _serve_prefix_bench(on_tpu, device):
    """Prefix-cache KV reuse leg (BENCH_SERVE_PREFIX=1): the
    prefix-heavy open-loop trace (make_prefix_trace — shared system-
    prompt templates + fresh tails, 90% reuse) through (a) the plain
    engine (spec-off/prefix-off: every prompt prefills cold), (b) the
    SAME engine shape with the templates registered in a PrefixCache
    (admission longest-matches and prefill resumes AT the boundary),
    and (c) prefix + self-draft speculation combined (the full fast
    path every pool inherits).  Reports tok/s for all three, prefill
    dispatches saved (the ISSUE's >=50% bar), prefix hit counters, the
    compile-count pin, and the always-on exactness checks: prefix-hit
    streams bit-identical to cold streams for EVERY request; the
    combined engine's greedy streams vs cold and sampled streams vs
    its own run_solo."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import gpt2
    from paddle_tpu.serving import ServingEngine, make_prefix_trace

    class HP(gpt2.GPT2Config):
        vocab_size = 8000 if on_tpu else 200
        n_ctx = 256 if on_tpu else 128
        d_model = 256 if on_tpu else 64
        n_layer = 4 if on_tpu else 2
        n_head = 4 if on_tpu else 2
        dropout = 0.0

    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8 if on_tpu else 4))
    width = int(os.environ.get("BENCH_SERVE_WIDTH", 16 if on_tpu else 8))
    n_req = int(os.environ.get("BENCH_SERVE_PREFIX_REQS",
                               48 if on_tpu else 24))
    rate = float(os.environ.get("BENCH_SERVE_RATE", "2.0"))
    n_pfx = int(os.environ.get("BENCH_SERVE_PREFIXES", "2"))
    t_max = HP.n_ctx
    trace, prefixes = make_prefix_trace(
        n_req, rate, n_prefixes=n_pfx, prefix_len=t_max // 2,
        tail_len_range=(2, 6), out_len_range=(4, 8),
        vocab_size=HP.vocab_size,
        seed=int(os.environ.get("BENCH_SERVE_SEED", "0")),
        reuse_fraction=0.9, sampled_fraction=0.5)

    scope = fluid.Scope()
    out = {"requests": n_req, "prefixes": n_pfx,
           "prefix_len": t_max // 2}
    with fluid.scope_guard(scope):
        _, lm_startup, _, _ = gpt2.gpt2_logits_program(HP, seq_len=t_max)
        exe = fluid.Executor(fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace())
        lm_startup.random_seed = 23
        exe.run(lm_startup)

        def leg(key, eng, register):
            if register:
                for p in prefixes:
                    row = eng.register_prefix(p)
                    assert row is not None, "template shorter than chunk"
            eng.run(trace[:2])  # warm compile
            compiles_warm = exe.compile_count
            results, stats = eng.run(trace)
            out[key] = {
                "value": stats["tokens_per_s"],
                "unit": "new tokens/sec",
                "prefill_chunks": stats["prefill_chunks"],
                "steps": stats["steps"],
                "prefix_hit_rate": round(stats["prefix_hit_rate"], 4),
                "prefix_tokens_reused": stats["prefix_tokens_reused"],
                "retraces_during_run": exe.compile_count - compiles_warm,
            }
            sys.stderr.write(
                "SERVE_RESULT %s %s\n" % (key, json.dumps(out[key])))
            return results, stats

        cold_res, cold_stats = leg(
            "cold", ServingEngine(exe, HP, n_slots=slots, width=width,
                                  t_max=t_max), register=False)
        warm = ServingEngine(exe, HP, n_slots=slots, width=width,
                             t_max=t_max, prefix_rows=n_pfx)
        warm_res, warm_stats = leg("prefix", warm, register=True)
        both = ServingEngine(exe, HP, n_slots=slots, width=width,
                             t_max=t_max, prefix_rows=n_pfx,
                             draft="self",
                             spec_k=int(os.environ.get(
                                 "BENCH_SERVE_SPEC_K", "4")))
        both_res, both_stats = leg("prefix_plus_spec", both, register=True)
        out["prefix"]["accept_rate"] = 1.0
        out["prefix_plus_spec"]["accept_rate"] = round(
            both_stats["accept_rate"], 4)

        cold_tps = cold_stats["tokens_per_s"] or 1.0
        out["speedup_prefix_vs_cold"] = round(
            warm_stats["tokens_per_s"] / cold_tps, 2)
        out["speedup_prefix_plus_spec_vs_cold"] = round(
            both_stats["tokens_per_s"] / cold_tps, 2)
        out["prefill_chunks_saved_pct"] = round(
            100.0 * (1.0 - warm_stats["prefill_chunks"]
                     / max(1, cold_stats["prefill_chunks"])), 1)

        # exactness rides the bench: a prefix hit must be invisible in
        # the tokens (same KV bytes), for every request in the trace;
        # the combined engine holds the same contract for greedy rows
        # and the keyed run_solo contract for sampled rows
        mismatches = sum(
            0 if np.array_equal(warm_res[r.rid]["tokens"],
                                cold_res[r.rid]["tokens"]) else 1
            for r in trace)
        solo_budget = 4
        for r in trace:
            got = np.asarray(both_res[r.rid]["tokens"])
            if r.greedy:
                if not np.array_equal(got, cold_res[r.rid]["tokens"]):
                    mismatches += 1
            elif solo_budget > 0:
                ref, _ = both.run_solo(r)
                if not np.array_equal(got, np.asarray(ref)):
                    mismatches += 1
                solo_budget -= 1
        out["exactness_mismatches"] = mismatches
        sys.stderr.write(
            "SERVE_RESULT prefix_speedup %s saved_pct %s mismatches %d\n"
            % (out["speedup_prefix_vs_cold"],
               out["prefill_chunks_saved_pct"], mismatches))
    return out


def _serve_tp_bench(on_tpu, device):
    """GSPMD tensor-parallel serving leg (BENCH_SERVE_TP=1): the SAME
    seeded Poisson trace through (a) the single-device engine and (b) a
    ServingEngine(mesh=...) whose weights + KV slot-pool shard over an
    `mp` mesh (BENCH_SERVE_TP_WAYS devices, default 2 — on CPU run
    under XLA_FLAGS=--xla_force_host_platform_device_count=N, the PR 6
    virtual-device recipe).  Reports tok/s for both, the pool's
    per-device HBM footprint (the point: max-device bytes drop ~1/N vs
    the unsharded pool), comm-bytes attribution from the compiled HLO's
    collectives, which rule-table entries fell back to replication, and
    a pooled-vs-solo exactness sweep through the SHARDED engine (the
    PR 9 contract must survive sharding)."""
    import numpy as np

    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import gpt2
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.serving import ServingEngine, make_poisson_trace

    ways = int(os.environ.get("BENCH_SERVE_TP_WAYS", "2"))
    if len(jax.devices()) < ways:
        return {"skipped":
                "needs %d devices; run under XLA_FLAGS="
                "--xla_force_host_platform_device_count=%d"
                % (ways, ways)}

    class HP(gpt2.GPT2Config):
        vocab_size = 8000 if on_tpu else 256
        n_ctx = 256 if on_tpu else 64
        d_model = 256 if on_tpu else 64
        n_layer = 4 if on_tpu else 2
        n_head = 4 if on_tpu else 2
        dropout = 0.0

    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8 if on_tpu else 4))
    width = int(os.environ.get("BENCH_SERVE_WIDTH", 16 if on_tpu else 8))
    n_req = int(os.environ.get("BENCH_SERVE_REQS", 32 if on_tpu else 16))
    rate = float(os.environ.get("BENCH_SERVE_RATE", "2.0"))
    t_max = HP.n_ctx
    trace = make_poisson_trace(
        n_req, rate,
        prompt_len_range=(4, t_max // 4),
        out_len_range=(4, t_max // 4),
        vocab_size=HP.vocab_size,
        seed=int(os.environ.get("BENCH_SERVE_SEED", "0")),
        sampled_fraction=0.5)
    out = {"ways": ways, "slots": slots, "width": width,
           "requests": n_req}

    def run_engine(mesh):
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            _, lm_startup, _, _ = gpt2.gpt2_logits_program(
                HP, seq_len=t_max)
            exe = fluid.Executor(
                fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace())
            lm_startup.random_seed = 23
            exe.run(lm_startup)
            eng = ServingEngine(exe, HP, n_slots=slots, width=width,
                                t_max=t_max, mesh=mesh)
            eng.run(trace[:2])  # warm compile
            warm = exe.compile_count
            results, stats = eng.run(trace)
            pool = eng.kv_pool_bytes(scope)
            leg = {
                "value": stats["tokens_per_s"],
                "unit": "new tokens/sec",
                "occupancy_pct": stats["occupancy_pct"],
                "new_tokens": stats["new_tokens"],
                "steps": stats["steps"],
                "pool_bytes_total": pool["total_bytes"],
                "pool_bytes_max_device": pool["max_device_bytes"],
                "retraces_during_run": exe.compile_count - warm,
            }
            if mesh is not None:
                # exactness sweep rides the sharded leg: pooled == solo
                # through the SAME sharded program, request for request
                mism = 0
                for r in trace:
                    solo, _ = eng.run_solo(r)
                    if not np.array_equal(results[r.rid]["tokens"],
                                          solo):
                        mism += 1
                leg["exactness_mismatches"] = mism
                leg["comm"] = exe.spmd_comm_stats(eng.step_main)
                leg["replicated_fallbacks"] = [
                    list(x) for x in
                    eng.partition_rules.replicated_log]
        return leg

    out["unsharded"] = run_engine(None)
    sys.stderr.write("SERVE_TP_RESULT unsharded %s\n"
                     % json.dumps(out["unsharded"]))
    mesh = make_mesh({"mp": ways}, devices=jax.devices()[:ways])
    out["sharded"] = run_engine(mesh)
    sys.stderr.write("SERVE_TP_RESULT sharded %s\n"
                     % json.dumps(out["sharded"]))
    base = out["unsharded"]["pool_bytes_max_device"] or 1
    out["pool_bytes_per_device_vs_unsharded"] = round(
        out["sharded"]["pool_bytes_max_device"] / base, 4)
    out["tok_s_ratio_vs_unsharded"] = round(
        out["sharded"]["value"] / (out["unsharded"]["value"] or 1.0), 3)
    sys.stderr.write(
        "SERVE_TP_RESULT pool_bytes/device ratio %s tok/s ratio %s\n"
        % (out["pool_bytes_per_device_vs_unsharded"],
           out["tok_s_ratio_vs_unsharded"]))
    return out


def _spmd_train_bench(on_tpu, device):
    """GSPMD tensor-parallel TRAINING leg (BENCH_SPMD_TRAIN=1): the gpt2
    causal-LM builder stamped over dp x mp meshes {(2,1),(1,2),(2,2)}
    (needs BENCH_SPMD_TRAIN_DEVICES devices, default 4 — on CPU run
    under XLA_FLAGS=--xla_force_host_platform_device_count=N) vs the
    same program unstamped.  Per mesh: step/s, final-loss parity vs the
    unsharded run, the per-DEVICE peak-activation estimate (the global
    utils.memory_analysis estimate divided by the mesh size — the same
    scaling maybe_remat applies to the HBM budget), per-device
    param+optimizer-state bytes (the ZeRO point: matrices split 1/mp),
    and comm-bytes attribution from the compiled step's collectives."""
    import numpy as np

    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import gpt2
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.utils import memory_analysis as ma

    need = int(os.environ.get("BENCH_SPMD_TRAIN_DEVICES", "4"))
    if len(jax.devices()) < need:
        return {"skipped":
                "needs %d devices; run under XLA_FLAGS="
                "--xla_force_host_platform_device_count=%d"
                % (need, need)}

    class HP(gpt2.GPT2Config):
        vocab_size = 8000 if on_tpu else 256
        n_ctx = 256 if on_tpu else 32
        d_model = 256 if on_tpu else 64
        n_layer = 4 if on_tpu else 2
        n_head = 4
        d_inner = 1024 if on_tpu else 128
        dropout = 0.0
        tie_embeddings = False

    seq = int(os.environ.get("BENCH_SPMD_TRAIN_SEQ",
                             HP.n_ctx // 2))
    batch = int(os.environ.get("BENCH_SPMD_TRAIN_BATCH",
                               16 if on_tpu else 8))
    steps = int(os.environ.get("BENCH_SPMD_TRAIN_STEPS",
                               20 if on_tpu else 4))

    def run_leg(mesh_shape):
        mesh = None
        n_shards = 1
        if mesh_shape is not None:
            dp, mp = mesh_shape
            n_shards = dp * mp
            mesh = make_mesh({"dp": dp, "mp": mp},
                             devices=jax.devices()[:n_shards])
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            main, startup, feeds, fetches = gpt2.gpt2_lm_program(
                HP, seq_len=seq, lr=3e-4, mesh=mesh)
            exe = fluid.Executor(
                fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace())
            startup.random_seed = 23
            exe.run(startup)
            fb = gpt2.make_fake_lm_batch(batch, seq, HP, seed=0)
            exe.run(main, feed=fb, fetch_list=fetches)  # warm compile
            t0 = time.time()
            loss = None
            for _ in range(steps):
                out = exe.run(main, feed=fb, fetch_list=fetches)
                loss = float(np.asarray(out[0]).reshape(-1)[0])
            dt = time.time() - t0
            # per-device param + optimizer state (ZeRO leg)
            per_device = replicated = 0
            for n in scope.all_var_names():
                v = scope.find_var(n)
                if v is None or not hasattr(v, "sharding"):
                    continue
                replicated += v.nbytes
                nb = v.dtype.itemsize
                for d in v.sharding.shard_shape(v.shape):
                    nb *= int(d)
                per_device += nb
            # activation estimate: the estimator traces the GLOBAL
            # program, so per-device is the mesh-size scaling
            try:
                est = ma.estimate_peak_activation_bytes(
                    main, ma.program_feed_specs(
                        main, feeds, batch_hint=batch),
                    fetches[0].name)
                peak = est["peak_bytes"]
            except Exception as e:
                sys.stderr.write("peak estimate failed: %r\n" % (e,))
                peak = 0
            leg = {
                "value": round(steps / dt, 3),
                "unit": "steps/sec",
                "final_loss": loss,
                "state_bytes_per_device": int(per_device),
                "state_bytes_replicated": int(replicated),
                "peak_activation_bytes_global": int(peak),
                "peak_activation_bytes_per_device_est":
                    int(peak // n_shards),
            }
            if mesh is not None:
                leg["comm"] = exe.spmd_comm_stats(main)
        return leg

    out = {"batch": batch, "seq_len": seq, "steps": steps}
    out["unsharded"] = run_leg(None)
    sys.stderr.write("SPMD_TRAIN_RESULT unsharded %s\n"
                     % json.dumps(out["unsharded"]))
    base_loss = out["unsharded"]["final_loss"]
    base_bytes = out["unsharded"]["state_bytes_per_device"] or 1
    for dp, mp in ((2, 1), (1, 2), (2, 2)):
        key = "dp%d_mp%d" % (dp, mp)
        leg = run_leg((dp, mp))
        leg["loss_vs_unsharded"] = (
            None if base_loss in (None, 0.0)
            else round(abs(leg["final_loss"] - base_loss)
                       / abs(base_loss), 8))
        leg["state_bytes_per_device_vs_unsharded"] = round(
            leg["state_bytes_per_device"] / base_bytes, 4)
        out[key] = leg
        sys.stderr.write("SPMD_TRAIN_RESULT %s %s\n"
                         % (key, json.dumps(leg)))
    return out


def _pp_bench_program(on_tpu, seq):
    """The pp bench builder, split out so the pinned-cache test can
    reconstruct the exact program signature the BENCH_SPMD_PP leg
    consults the program tuner with."""
    from paddle_tpu.models import gpt2

    class HP(gpt2.GPT2Config):
        vocab_size = 8000 if on_tpu else 256
        n_ctx = 256 if on_tpu else 32
        d_model = 256 if on_tpu else 64
        n_layer = 6          # deep enough that 4 stages stay balanced
        n_head = 4
        d_inner = 1024 if on_tpu else 128
        dropout = 0.0
        tie_embeddings = False

    main, startup, feeds, fetches = gpt2.gpt2_lm_program(
        HP, seq_len=seq, lr=3e-4)
    return HP, main, startup, feeds, fetches


def _spmd_pp_bench(on_tpu, device):
    """Pipeline-parallel TRAINING leg (BENCH_SPMD_PP=1): the gpt2
    causal-LM builder stage-sliced over a (dp, mp, pp) mesh — default
    (1, 1, 4), needs BENCH_SPMD_PP_DEVICES devices (4; on CPU run under
    XLA_FLAGS=--xla_force_host_platform_device_count=N) — under BOTH
    microbatch schedules vs the same program unpipelined.  Per
    schedule: step/s, final-loss parity, per-device param+opt-state
    bytes from pipeline_state_report (the 1/S memory point the
    acceptance bar reads), and the schedule's peak activation residency
    from pipeline_activation_report (the O(M) GPipe vs O(S) 1F1B
    claim, measured).  M consults the program tuning cache
    (n_microbatches, a consult-only knob BENCH_SPMD_PP itself
    deposits); BENCH_SPMD_PP_MICROBATCHES overrides."""
    import numpy as np

    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import gpt2
    from paddle_tpu.transpiler import autotune as at
    from paddle_tpu.transpiler.pipeline import (
        pipeline_activation_report, pipeline_program,
        pipeline_state_report)
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.utils import memory_analysis as ma

    need = int(os.environ.get("BENCH_SPMD_PP_DEVICES", "4"))
    if len(jax.devices()) < need:
        return {"skipped":
                "needs %d devices; run under XLA_FLAGS="
                "--xla_force_host_platform_device_count=%d"
                % (need, need)}

    mesh_shape = tuple(int(x) for x in os.environ.get(
        "BENCH_SPMD_PP_MESH", "1,1,4").split(","))
    dp, mp, pp = mesh_shape
    seq = int(os.environ.get("BENCH_SPMD_PP_SEQ",
                             (256 if on_tpu else 32) // 2))
    batch = int(os.environ.get("BENCH_SPMD_PP_BATCH",
                               16 if on_tpu else 8))
    steps = int(os.environ.get("BENCH_SPMD_PP_STEPS",
                               20 if on_tpu else 4))

    def run_leg(schedule, M):
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            HP, main, startup, feeds, fetches = _pp_bench_program(
                on_tpu, seq)
            if schedule is not None:
                axes = {"pp": pp}
                if dp > 1:
                    axes = {"dp": dp, "pp": pp}
                mesh = make_mesh(axes,
                                 devices=jax.devices()[:dp * mp * pp])
                main = pipeline_program(main, mesh, n_microbatches=M,
                                        schedule=schedule)
            exe = fluid.Executor(
                fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace())
            startup.random_seed = 23
            exe.run(startup)
            fb = gpt2.make_fake_lm_batch(batch, seq, HP, seed=0)
            exe.run(main, feed=fb, fetch_list=fetches)  # warm compile
            t0 = time.time()
            loss = None
            for _ in range(steps):
                out = exe.run(main, feed=fb, fetch_list=fetches)
                loss = float(np.asarray(out[0]).reshape(-1)[0])
            dt = time.time() - t0
            leg = {
                "value": round(steps / dt, 3),
                "unit": "steps/sec",
                "final_loss": loss,
            }
            if schedule is not None:
                srep = pipeline_state_report(main)
                arep = pipeline_activation_report(main)
                leg["state_bytes_per_device"] = int(
                    srep["per_device_peak_bytes"])
                leg["state_bytes_single_device"] = int(
                    srep["single_device_bytes"])
                leg["state_ratio_vs_single_device"] = round(
                    srep["peak_ratio"], 4)
                leg["peak_activation_bytes"] = int(
                    arep[schedule]["peak_bytes"])
        return leg

    # the tuner pins M per (program signature, shape bucket): consult
    # it the way a training driver would (CI: the pinned cache entry)
    _, probe, _, feeds, _ = _pp_bench_program(on_tpu, seq)
    spec = ma.program_feed_specs(probe, feeds, batch_hint=batch)
    decision = at.tune(probe, spec)
    M = int(os.environ.get(
        "BENCH_SPMD_PP_MICROBATCHES",
        at.pipeline_knobs(decision).get("n_microbatches", 8)))

    out = {"batch": batch, "seq_len": seq, "steps": steps,
           "mesh_shape": list(mesh_shape), "n_microbatches": M}
    out["unpipelined"] = run_leg(None, M)
    sys.stderr.write("SPMD_PP_RESULT unpipelined %s\n"
                     % json.dumps(out["unpipelined"]))
    base_loss = out["unpipelined"]["final_loss"]
    for sched in ("gpipe", "1f1b"):
        leg = run_leg(sched, M)
        leg["loss_vs_unpipelined"] = (
            None if base_loss in (None, 0.0)
            else round(abs(leg["final_loss"] - base_loss)
                       / abs(base_loss), 8))
        out[sched] = leg
        sys.stderr.write("SPMD_PP_RESULT %s %s\n"
                         % (sched, json.dumps(leg)))
    # deposit the consult-only knobs for the next consult (a searched=
    # False entry never lands on disk, so only note the decision here)
    out["tuned_decision"] = {
        "mesh_shape": list(mesh_shape), "n_microbatches": M}
    return out


def _fabric_bench(on_tpu, device):
    """Serving-fabric leg (BENCH_FABRIC=1): the SAME seeded Poisson
    trace through a FabricRouter three ways — (a) a static 3-pool
    fleet, (b) the deterministic 1->3->1 pool-schedule walk, (c) 3
    pools with one pool_kill mid-stream (pinned PADDLE_TPU_FAULT_SEED)
    — reporting fleet new-tokens/s, p50/p99 request latency in fabric
    steps, rejection rate, re-placed-request count, and per-pool
    occupancy.  The chaos leg also verifies every re-placed stream
    completed (the failover exactness bar rides the tests; the bench
    pins the degradation numbers)."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.distributed.faults import FaultSchedule
    from paddle_tpu.models import gpt2
    from paddle_tpu.serving import FabricRouter, make_poisson_trace

    class HP(gpt2.GPT2Config):
        vocab_size = 8000 if on_tpu else 200
        n_ctx = 256 if on_tpu else 64
        d_model = 256 if on_tpu else 64
        n_layer = 4 if on_tpu else 2
        n_head = 4 if on_tpu else 2
        dropout = 0.0

    slots = int(os.environ.get("BENCH_FABRIC_SLOTS", 8 if on_tpu else 2))
    width = int(os.environ.get("BENCH_SERVE_WIDTH", 16 if on_tpu else 8))
    n_req = int(os.environ.get("BENCH_FABRIC_REQS", 48 if on_tpu else 24))
    rate = float(os.environ.get("BENCH_FABRIC_RATE", "1.5"))
    t_max = HP.n_ctx

    def trace():
        return make_poisson_trace(
            n_req, rate,
            prompt_len_range=(4, t_max // 8),
            out_len_range=(4, t_max // 8),
            vocab_size=HP.vocab_size,
            seed=int(os.environ.get("BENCH_SERVE_SEED", "0")),
            sampled_fraction=0.5)

    from paddle_tpu.serving import ServingEngine

    def factory():
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            _, lm_startup, _, _ = gpt2.gpt2_logits_program(
                HP, seq_len=t_max)
            exe = fluid.Executor(
                fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace())
            lm_startup.random_seed = 23
            exe.run(lm_startup)
            eng = ServingEngine(exe, HP, n_slots=slots, width=width,
                                t_max=t_max)
        return eng, scope

    def pct(vals, p):
        return vals[min(len(vals) - 1, int(p * len(vals)))]

    def metrics(results, stats):
        lat = sorted(r["latency_steps"] for r in results.values()
                     if r["status"] == "OK")
        ok = sum(r["status"] == "OK" for r in results.values())
        return {
            "value": stats["tokens_per_s"],
            "unit": "new tokens/sec",
            "ok": ok,
            "requests": n_req,
            "p50_latency_steps": pct(lat, 0.50) if lat else None,
            "p99_latency_steps": pct(lat, 0.99) if lat else None,
            "rejection_rate": stats["rejection_rate"],
            "replaced": stats["replaced"],
            "pools_added": stats["pools_added"],
            "pools_retired": stats["pools_retired"],
            "pools_died": stats["pools_died"],
            "occupancy": stats["occupancy"],
            "per_pool_occupancy": {
                pid: p["mean_occupancy"]
                for pid, p in stats["pools"].items()},
            "fabric_steps": stats["step"],
        }

    def leg(n_pools, schedule=None, faults=None):
        # depth sized to the workload: the bench pins latency under
        # load, the loud-rejection contract is pinned by the tests
        router = FabricRouter(factory, n_pools=n_pools,
                              queue_depth=n_req,
                              fault_schedule=faults)
        results, stats = router.run(trace(), pool_schedule=schedule)
        return metrics(results, stats)

    # --- process-mode legs: REAL pool-worker subprocesses over RPC ---
    proc_hp = {"vocab_size": HP.vocab_size, "n_ctx": HP.n_ctx,
               "d_model": HP.d_model, "n_layer": HP.n_layer,
               "n_head": HP.n_head, "dropout": 0.0}

    def proc_factory():
        from paddle_tpu.serving import spawn_pool_worker

        # workers always decode on CPU: N extra processes must not
        # contend for the chip the in-process legs are benching
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        return spawn_pool_worker(hp_overrides=proc_hp, n_slots=slots,
                                 width=width, t_max=t_max, seed=23,
                                 env=env)

    def proc_leg(n_pools, faults=None):
        import time as _t

        from paddle_tpu.distributed.rpc import CallPolicy

        router = FabricRouter(
            proc_factory, n_pools=n_pools, queue_depth=n_req,
            pool_mode="process",
            rpc_policy=CallPolicy(timeout_s=5.0, deadline_s=10.0,
                                  attempts=2,
                                  verb_deadlines={"submit": 5.0,
                                                  "shutdown": 2.0}),
            fault_schedule=faults)
        # RPC-hop overhead: round-trips of the no-op `results` verb
        # against one idle worker — the pure wire cost every fabric
        # step pays per pool on top of the engine step itself
        h0 = sorted(router.pools.values(), key=lambda h: h.pid)[0]
        hops = []
        for _ in range(50):
            t0 = _t.perf_counter()
            h0.engine.policy.call(h0.engine._cli, "results", ack=[])
            hops.append((_t.perf_counter() - t0) * 1e3)
        hops.sort()
        try:
            results, stats = router.run(trace())
        finally:
            for h in list(router.pools.values()):
                h.engine.close(kill=False)
        m = metrics(results, stats)
        m["rpc_hop_ms_p50"] = round(pct(hops, 0.50), 3)
        m["rpc_hop_ms_p99"] = round(pct(hops, 0.99), 3)
        return m

    out = {"slots": slots, "width": width, "requests": n_req,
           "rate": rate}
    out["static_3_pool"] = leg(3)
    sys.stderr.write("FABRIC_RESULT static_3_pool %s\n"
                     % json.dumps(out["static_3_pool"]))
    grow_t = max(2, int(n_req / (3 * rate)))
    shrink_t = 4 * grow_t
    out["scale_1_3_1"] = leg(1, schedule=[(grow_t, +2),
                                          (shrink_t, -2)])
    out["scale_1_3_1"]["schedule"] = "%d:+2,%d:-2" % (grow_t, shrink_t)
    sys.stderr.write("FABRIC_RESULT scale_1_3_1 %s\n"
                     % json.dumps(out["scale_1_3_1"]))
    seed = int(os.environ.get("PADDLE_TPU_FAULT_SEED", "0"))
    kill_t = max(3, grow_t)
    out["chaos_pool_kill"] = leg(
        3, faults=FaultSchedule({"fabric": {kill_t: "pool_kill"}},
                                seed=seed))
    out["chaos_pool_kill"]["fault_seed"] = seed
    out["chaos_pool_kill"]["kill_step"] = kill_t
    sys.stderr.write("FABRIC_RESULT chaos_pool_kill %s\n"
                     % json.dumps(out["chaos_pool_kill"]))
    # (d) the SAME trace through 3 REAL worker processes (CPU decode)
    # — tok/s vs the in-process fleet plus the per-hop RPC overhead —
    # and (e) its chaos twin with ONE worker SIGKILL'd mid-stream
    # (pool_proc_kill): detection bounded by the CallPolicy deadline,
    # every stream still completes via the replay path
    out["process_3_pool"] = proc_leg(3)
    sys.stderr.write("FABRIC_RESULT process_3_pool %s\n"
                     % json.dumps(out["process_3_pool"]))
    out["chaos_proc_kill"] = proc_leg(
        3, faults=FaultSchedule({"fabric": {kill_t: "pool_proc_kill"}},
                                seed=seed))
    out["chaos_proc_kill"]["fault_seed"] = seed
    out["chaos_proc_kill"]["kill_step"] = kill_t
    sys.stderr.write("FABRIC_RESULT chaos_proc_kill %s\n"
                     % json.dumps(out["chaos_proc_kill"]))
    base = out["static_3_pool"]["p99_latency_steps"] or 1
    if out["scale_1_3_1"]["p99_latency_steps"] is not None:
        out["p99_ratio_scaled_vs_static"] = round(
            out["scale_1_3_1"]["p99_latency_steps"] / float(base), 3)
    if out["static_3_pool"]["value"]:
        out["process_vs_inproc_tps_ratio"] = round(
            out["process_3_pool"]["value"]
            / float(out["static_3_pool"]["value"]), 3)
    return out


def _dist_smokes():
    """pserver-mode and collective (nccl2-analog) throughput smokes on
    localhost CPU subprocesses (fluid_benchmark.py --update_method
    pserver|nccl2 matrix).  Wall-clock steps/sec including transport."""
    import time as _t

    here = os.path.dirname(os.path.abspath(__file__))
    steps = int(os.environ.get("BENCH_DIST_STEPS", "8"))
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "DIST_STEPS": str(steps)})
    out = {}
    pserver_cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
                   "--mode", "pserver", "--nproc", "2",
                   "--pservers", "2", "tests/dist_mlp.py"]
    legs = {
        "pserver_2x2": (pserver_cmd, {"DIST_MODEL": ""}),
        # distributed lookup table: prefetch + sparse-update RPC path
        "pserver_sparse_2x2": (pserver_cmd, {"DIST_MODEL": "sparse"}),
        # durable async sparse at HIGH ROW-CHURN (ctr_deepfm, fresh
        # uniform ids every step): the async listen_and_serv path with
        # the write-ahead journal armed (ephemeral ckpt dir) — COUNTERS
        # carry async_sparse_sends/dedup/resends + recovery_ms, and the
        # PSERVER-STATS aggregation below reports journal bytes/step
        "pserver_sparse_async_2x2": (
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--mode", "pserver", "--async-mode", "--nproc", "2",
             "--pservers", "2", "tests/dist_ctr.py"],
            {"DIST_EPHEMERAL_CKPT": "1"}),
        "collective_2": ([sys.executable, "-m",
                          "paddle_tpu.distributed.launch",
                          "--nproc", "2", "tests/launch_worker.py"], {}),
        # collective dense-grad backend: SAME dist MLP as pserver_2x2,
        # dense sync as in-step c_allreduce over the 2-process mesh —
        # COUNTERS must show zero rpc round trips
        "collective_2x": ([sys.executable, "-m",
                           "paddle_tpu.distributed.launch",
                           "--mode", "collective", "--nproc", "2",
                           "tests/dist_mlp.py"],
                          {"DIST_MODE": "collective"}),
        # elastic autoscaling: the supervisor's scheduled driver scales
        # 2 -> 4 -> 2 trainers mid-run (grow before the originals can
        # finish, shrink the grown ranks again); PSERVER-STATS phases
        # report per-membership steps/s (world * rounds / wall) and
        # COUNTERS carry the re-plan count + latency.  Single repeat:
        # the leg IS a membership trace, not a steady-state median.
        "pserver_elastic_2to4": (
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--mode", "pserver", "--nproc", "2", "--pservers", "2",
             "--supervise", "--elastic", "2:4",
             "--elastic-schedule", "4:+2,22:-2", "tests/dist_mlp.py"],
            {"DIST_STEPS": "80", "DIST_STEP_SLEEP": "0.25",
             "BENCH_LEG_REPEATS": "1"}),
        # live pserver shard migration: the pserver SET changes
        # 2 -> 3 -> 2 mid-run via the two-phase journaled handoff
        # (migrate_begin/commit); reports per-epoch steps/s (phases —
        # the handoff's throughput dip is phase-visible), migration_ms
        # and bytes moved per handoff, plus the server-side
        # migrated_bytes/shards counters.  Single repeat: the leg IS a
        # membership trace, not a steady-state median.
        "pserver_migrate": (
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--mode", "pserver", "--nproc", "2", "--pservers", "2",
             "--elastic-pservers", "2:3",
             "--pserver-schedule", "5:+1,13:-1", "tests/dist_mlp.py"],
            {"DIST_STEPS": "48", "DIST_STEP_SLEEP": "0.25",
             "DIST_MODEL": "sparse", "BENCH_LEG_REPEATS": "1"}),
    }
    # BENCH_DIST_ONLY=<leg> runs a single dist leg (targeted A/Bs and
    # the elastic-membership trace without the full matrix)
    only = os.environ.get("BENCH_DIST_ONLY")
    if only:
        if only not in legs:
            # a typo must not read as "nothing regressed"
            raise ValueError(
                "BENCH_DIST_ONLY=%r is not a dist leg (have: %s)"
                % (only, sorted(legs)))
        legs = {only: legs[only]}
    # VERDICT weak #5: one-shot wall-clock on a noisy localhost made the
    # pserver legs unreproducible — pin the step count, run N repeats,
    # report the MEDIAN with the spread so a regression is a signal, not
    # a coin flip
    repeats = max(1, int(os.environ.get("BENCH_DIST_REPEATS", "3")))
    for name, (cmd, overrides) in legs.items():
        leg_env = dict(env)
        # stray shell vars must not silently flip a leg's model
        for k in ("DIST_MODEL", "DIST_SPARSE_IDS", "DIST_OPTIMIZER",
                  "DIST_MODE", "DIST_COLLECTIVE_DEVICES",
                  "DIST_EPHEMERAL_CKPT", "DIST_FIELD_DIM", "DIST_FIELDS",
                  "DIST_STEPS", "DIST_STEP_SLEEP"):
            leg_env.pop(k, None)
        leg_env["DIST_STEPS"] = str(steps)
        leg_env.update({k: v for k, v in overrides.items() if v})
        # leg-local step count / repeat override (the elastic leg runs a
        # fixed membership trace once, not a steady-state median)
        leg_steps = int(leg_env.get("DIST_STEPS", steps))
        leg_repeats = int(overrides.get("BENCH_LEG_REPEATS", repeats))
        leg_env.pop("BENCH_LEG_REPEATS", None)
        vals, err, counters, phases = [], None, None, None
        migrations = []
        for _rep in range(leg_repeats):
            t0 = _t.time()
            try:
                proc = subprocess.run(
                    cmd, cwd=here, env=leg_env, timeout=600,
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                )
                dt = _t.time() - t0
                if proc.returncode != 0:
                    err = {"error": "rc=%d: %s" % (
                        proc.returncode,
                        proc.stdout[-300:].decode("utf-8", "replace"))}
                    break
                vals.append(leg_steps / dt)
                # deterministic comm evidence: every trainer prints a
                # COUNTERS json line (round trips / bytes / feed ms) —
                # summed across trainers, they are a property of the op
                # plan, so a regression shows without wall-clock noise
                agg = {}
                ps_agg = {}
                for ln in proc.stdout.decode("utf-8", "replace").splitlines():
                    # launch.py prefixes child lines with "[trainer.N] "
                    # (and "[pserver.N] " for the server-side stats the
                    # async journal/staleness evidence rides on)
                    pos = ln.find("PSERVER MIGRATION ok:")
                    if pos >= 0:
                        # the migration driver's summary: world size,
                        # shards + bytes moved, handoff wall time
                        import re as _re

                        m = _re.search(
                            r"world=(\d+) moved=(\d+) bytes=(\d+) "
                            r"ms=([0-9.]+)"
                            r"(?: freeze_ms=([0-9.]+))?", ln)
                        if m:
                            mig = {
                                "world": int(m.group(1)),
                                "moved_shards": int(m.group(2)),
                                "bytes": int(m.group(3)),
                                "migration_ms": float(m.group(4))}
                            if m.group(5) is not None:
                                # delta handoff: the frozen window is
                                # the tail only, a fraction of the
                                # full wall time
                                mig["freeze_ms"] = float(m.group(5))
                            migrations.append(mig)
                        continue
                    pos = ln.find("PSERVER-STATS ")
                    if pos >= 0:
                        try:
                            s = json.loads(
                                ln[pos + len("PSERVER-STATS "):])
                        except ValueError:
                            continue
                        # elastic leg: the membership phase log (keep
                        # the richest one across servers/repeats)
                        ph = s.get("phases")
                        if isinstance(ph, list) and (
                                phases is None or len(ph) > len(phases)):
                            phases = ph
                        for k, v in s.items():
                            if k in ("journal_records", "journal_bytes",
                                     "journal_replayed",
                                     "journal_tail_skips", "dedup_drops",
                                     "staleness_parks", "parked_ms",
                                     "async_sends",
                                     # live shard migration evidence
                                     "migrations_out", "migrations_in",
                                     "migrated_bytes_out",
                                     "migrated_bytes_in",
                                     "migrated_shards_out",
                                     "migrate_aborts",
                                     "stale_plan_drops"):
                                ps_agg[k] = round(ps_agg.get(k, 0) + v, 3)
                        continue
                    pos = ln.find("COUNTERS ")
                    if pos < 0:
                        continue
                    try:
                        c = json.loads(ln[pos + len("COUNTERS "):])
                    except ValueError:
                        continue
                    for k, v in c.items():
                        if isinstance(v, (int, float)):
                            agg[k] = round(agg.get(k, 0) + v, 3)
                        else:
                            # tags (wire_dtype) ride along un-summed
                            agg.setdefault(k, v)
                if ps_agg.get("journal_bytes"):
                    agg["journal_bytes_per_step"] = round(
                        ps_agg["journal_bytes"] / float(leg_steps), 1)
                if ps_agg:
                    agg.update({"ps_" + k: v for k, v in ps_agg.items()})
                if agg:
                    counters = agg
            except subprocess.TimeoutExpired:
                err = {"error": "timeout"}
                break
        if err is not None:
            out[name] = err
        else:
            import statistics

            out[name] = {
                "value": round(statistics.median(vals), 3),
                "unit": "steps/sec (localhost cpu, median of %d)"
                        % leg_repeats,
                "steps": leg_steps,
                "repeats": leg_repeats,
                "spread": round(max(vals) - min(vals), 3),
                "samples": [round(v, 3) for v in vals],
            }
            if counters is not None:
                out[name]["counters"] = counters
            if phases:
                # per-membership throughput: world trainers each advance
                # one step per round, so a phase's aggregate steps/s is
                # world * rounds / wall — THE "steps/s tracks the
                # trainer count" evidence, plus re-plan latency off the
                # summed COUNTERS
                out[name]["phases"] = phases
                out[name]["steps_per_s_by_phase"] = [
                    {"world": p["world"],
                     "steps_per_s": round(
                         p["world"] * p["rounds"] / p["wall_s"], 2)}
                    for p in phases
                    if p.get("rounds") and p.get("wall_s")]
                if counters and counters.get("replans"):
                    out[name]["replan_ms_mean"] = round(
                        counters["replan_ms"] / counters["replans"], 2)
            if migrations:
                # live shard migration: per-handoff wall time + payload
                # (steps/s across the handoff rides the phases above —
                # each migration mints an epoch, so the handoff phase is
                # its own steps_per_s_by_phase row)
                out[name]["migrations"] = migrations
                out[name]["migration_ms_mean"] = round(
                    sum(m["migration_ms"] for m in migrations)
                    / len(migrations), 2)
                frz = [m["freeze_ms"] for m in migrations
                       if "freeze_ms" in m]
                if frz:
                    out[name]["freeze_ms_mean"] = round(
                        sum(frz) / len(frz), 2)
                out[name]["migrated_bytes_total"] = sum(
                    m["bytes"] for m in migrations)
    if only:
        return out
    # BASELINE config 5 dist leg: GPT-2 TP+DP step over the 8-device
    # virtual mesh (one process; a step-time artifact, not a scaling claim)
    env_tp = dict(env)
    flags = [f for f in env_tp.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append("--xla_force_host_platform_device_count=8")
    env_tp["XLA_FLAGS"] = " ".join(flags)
    try:
        proc = subprocess.run(
            [sys.executable, "scripts/gpt2_tp_step.py"], cwd=here,
            env=env_tp, timeout=600,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        lines = proc.stdout.decode("utf-8", "replace").strip().splitlines()
        # stderr is merged in: scan backwards for the JSON line instead
        # of trusting the tail (a trailing warning must not kill the run)
        parsed = None
        if proc.returncode == 0:
            for ln in reversed(lines):
                if not ln.strip().startswith("{"):
                    continue  # bare numbers / NaN also parse as JSON
                try:
                    cand = json.loads(ln)
                except ValueError:
                    continue
                if isinstance(cand, dict):
                    parsed = cand
                    break
        if parsed is not None:
            out["gpt2_tp_dp2xmp4"] = parsed
        else:
            out["gpt2_tp_dp2xmp4"] = {"error": "rc=%d: %s" % (
                proc.returncode,
                proc.stdout[-300:].decode("utf-8", "replace"))}
    except subprocess.TimeoutExpired:
        out["gpt2_tp_dp2xmp4"] = {"error": "timeout"}
    return out


def _transformer_bench(on_tpu, device):
    """Transformer-base (dist_transformer.py:123 config) tokens/sec + MFU."""
    import numpy as np
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.utils import flops as flops_util

    # bs128 x seq256 = 32k tokens/step (10.6 TFLOP): measured 3.4x the MFU
    # of the old bs32/seq64 diagnostic config, which at 2k tokens/step
    # never filled the chip (bs256 gave no further gain)
    batch = int(os.environ.get("BENCH_TFM_BATCH", 128 if on_tpu else 4))
    seq = int(os.environ.get("BENCH_TFM_SEQ", 256 if on_tpu else 16))
    steps = max(1, int(os.environ.get("BENCH_TFM_STEPS", 10 if on_tpu else 2)))
    warmup = 2 if on_tpu else 1
    # bf16 matmuls (MXU) + fused attention by default on the chip; under
    # FLAGS_use_pallas (BENCH_PALLAS, default ON on the chip) the fused
    # ops run the pallas kernel layer: flash attention, matmul-epilogue
    # fc/residual-LN fusions, and the logits-free fused cross-entropy.
    use_bf16 = os.environ.get("BENCH_TFM_BF16", "1" if on_tpu else "0") == "1"
    use_fused = os.environ.get("BENCH_TFM_FUSED", "1") == "1"
    from paddle_tpu.ops import kernel_tuning as _kt

    _kt.reset_attribution()  # this leg's attribution snapshot is its own

    class HP(tfm.ModelHyperParams):
        max_length = max(seq, tfm.ModelHyperParams.max_length)
        fused_attn = use_fused

    # BENCH_REMAT=<bytes>: build the leg under an HBM budget — the
    # builder's remat pass marks checkpoint segments until the estimated
    # fwd+bwd peak fits (1 = force maximal recompute); the leg reports
    # the estimator's before/after and trains WITH the recompute cost
    remat_budget = int(os.environ.get("BENCH_REMAT", "0"))
    main, startup, feeds, fetches = _build_tfm_leg(
        HP, seq, use_bf16, remat_budget)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace())
        exe.run(startup)
        batch_np = tfm.make_fake_batch(batch, seq, seq, HP, seed=0)
        feed = {k: jax.device_put(v, device) for k, v in batch_np.items()}
        for _ in range(warmup):
            out = exe.run(main, feed=feed, fetch_list=fetches)
        np.asarray(out[0])
        t0 = time.time()
        for _ in range(steps):
            out = exe.run(main, feed=feed, fetch_list=fetches, return_numpy=False)
        jax.block_until_ready(out)
        dt = time.time() - t0

        # BENCH_INNER=K: K steps in ONE compiled lax.scan — the delta vs
        # the headline is the per-step host dispatch tax (same
        # diagnostic as the resnet leg)
        inner = int(os.environ.get("BENCH_INNER", "0"))
        dt_in = None
        if inner > 0:
            o = exe.run_loop(inner, main, feed=feed, fetch_list=fetches,
                             return_numpy=False)
            jax.block_until_ready(o)  # compile + warm
            t0 = time.time()
            o = exe.run_loop(inner, main, feed=feed, fetch_list=fetches,
                             return_numpy=False)
            jax.block_until_ready(o)
            dt_in = time.time() - t0

    tokens = batch * seq * steps / dt
    step_flops = flops_util.program_flops(main, batch_hint=batch)
    mfu = flops_util.mfu(step_flops, steps, dt, device)
    out = {
        "metric": "transformer_base_train_tokens_per_sec_per_chip",
        "value": round(tokens, 1),
        "unit": "tokens/sec",
        "model_tflops_per_step": round(step_flops / 1e12, 3),
        "fused_counts": {
            "fc": getattr(main, "_fc_fused_count", 0),
            "residual_ln": getattr(main, "_residual_ln_fused_count", 0),
            "linear_xent": getattr(main, "_linear_xent_fused_count", 0),
        },
        "kernel_attribution": _kernel_attribution(),
    }
    if mfu is not None:
        out["mfu"] = round(mfu, 4)
    if dt_in is not None:
        tokens_in = batch * seq * inner / dt_in
        out["inner_loop"] = {
            "iters": inner,
            "tokens_per_sec": round(tokens_in, 1),
            "dispatch_tax_pct": round(
                max(0.0, 1 - tokens / tokens_in) * 100, 1),
        }
        m_in = flops_util.mfu(step_flops, inner, dt_in, device)
        if m_in is not None:
            out["inner_loop"]["mfu"] = round(m_in, 4)
    if remat_budget:
        # peak-HBM-estimate attribution for the remat leg
        out["remat"] = dict(getattr(main, "_remat_report", {}) or {})
    if os.environ.get("BENCH_AUTOTUNE", "0") == "1":
        out["autotune"] = _transformer_autotune_leg(
            HP, seq, batch, steps, on_tpu, device, remat_budget)
    return out


def _build_tfm_leg(hp, seq, bf16, budget):
    """Build the transformer leg under an HBM budget flag, restoring
    the PRIOR flag value (a user-set FLAGS_hbm_budget_bytes survives)."""
    from paddle_tpu import flags as _flags
    from paddle_tpu.models import transformer as tfm

    prior = _flags.get_flag("hbm_budget_bytes")
    _flags.set_flags({"hbm_budget_bytes": int(budget)})
    try:
        return tfm.wmt_transformer_program(
            hp, src_len=seq, trg_len=seq, use_bf16=bf16)
    finally:
        _flags.set_flags({"hbm_budget_bytes": prior})


def _transformer_autotune_leg(LegHP, seq, batch, steps, on_tpu, device,
                              remat_budget):
    """BENCH_AUTOTUNE=1: transpiler.autotune searches the program knob
    space for a transformer leg (decision cached at
    BENCH_PROGRAM_TUNE_CACHE / FLAGS_program_tune_cache), then the leg
    A/Bs the all-defaults config against the tuned one on REAL feeds and
    reports tuned-vs-default steps/s plus the steady-state retrace
    count (the no-retrace contract: zero).

    On CPU the A/B defaults to the LATENCY-REGIME transformer
    (BENCH_AT_DMODEL=128, BENCH_AT_LAYERS=2, BENCH_AT_VOCAB=4000; same
    batch/seq as the leg): the full transformer-base step on one CPU
    core is OPTIMIZER-bound (adam over 60M params is ~1.7 GB of memory
    traffic per 64-token step — no schedule knob can cut it; measured
    tuned speedup there ~1.04x from the dispatch window alone), while
    the latency regime is where the steps_per_dispatch knob is the
    binding constraint.  On a chip the full-size leg is the default
    (BENCH_AT_DMODEL=0): there use_pallas/AMP enter the search with MXU
    timings."""
    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import flags as _flags
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.transpiler import autotune as at

    cache_path = os.environ.get("BENCH_PROGRAM_TUNE_CACHE", "")
    if cache_path:
        _flags.set_flags({"program_tune_cache": cache_path})

    at_dmodel = int(os.environ.get("BENCH_AT_DMODEL",
                                   "0" if on_tpu else "128"))
    if at_dmodel > 0:
        n_layer = int(os.environ.get("BENCH_AT_LAYERS", "2"))
        vocab = int(os.environ.get("BENCH_AT_VOCAB", "4000"))

        class HP(LegHP):
            d_model = at_dmodel
            d_inner_hid = 4 * at_dmodel
            n_head = max(1, at_dmodel // 32)
            src_vocab_size = vocab
            trg_vocab_size = vocab

        # set outside the body: `n_layer = n_layer` in a class block
        # resolves the RHS via LOAD_NAME (no closure), not the enclosing
        # function local
        HP.n_layer = n_layer
    else:
        HP = LegHP

    def rebuild(decision):
        m, s, _f, fl = _build_tfm_leg(
            HP, seq, bool(decision.get("bf16_amp")),
            1 if decision.get("remat") else remat_budget)
        return m, s, fl

    main, startup, feeds, fetches = _build_tfm_leg(
        HP, seq, False, remat_budget)
    batch_np = tfm.make_fake_batch(batch, seq, seq, HP, seed=0)
    spec = {k: (tuple(v.shape), str(v.dtype)) for k, v in batch_np.items()}
    t0 = time.time()
    decision = at.tune(main, spec, startup=startup, fetches=fetches,
                       rebuild=rebuild, max_trials=8, steps=2, warmup=1)
    tune_s = time.time() - t0

    def measure(dec):
        """steps/s of a decision on the leg's REAL feeds, plus the
        steady-state retrace count across the timed phase."""
        m, s, fl = (rebuild(dec) if (dec.get("bf16_amp")
                                     or dec.get("remat")) else
                    (main, startup, fetches))
        saved = {k: _flags.get_flag(k) for k in ("prng_impl", "use_pallas")}
        _flags.set_flags(at.tuned_flags(dec))
        try:
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe = fluid.Executor(
                    fluid.TPUPlace(0) if on_tpu else fluid.CPUPlace())
                s.random_seed = 99
                exe.run(s)
                feed = {k: jax.device_put(v, device)
                        for k, v in batch_np.items()}
                window = int(dec.get("steps_per_dispatch", 1) or 1)
                n_win = max(1, steps // window)
                if window > 1:
                    o = exe.run_loop(window, m, feed=feed, fetch_list=fl,
                                     return_numpy=False)
                    jax.block_until_ready(o)
                    compiles0 = (exe.compile_count,
                                 len(getattr(exe, "_loop_cache", {}) or {}))
                    t0 = time.time()
                    for _ in range(n_win):
                        o = exe.run_loop(window, m, feed=feed,
                                         fetch_list=fl, return_numpy=False)
                    jax.block_until_ready(o)
                    dt = time.time() - t0
                    compiles1 = (exe.compile_count,
                                 len(getattr(exe, "_loop_cache", {}) or {}))
                    retraces = (compiles1[0] - compiles0[0]) + (
                        compiles1[1] - compiles0[1])
                    return n_win * window / dt, retraces
                for _ in range(2):
                    o = exe.run(m, feed=feed, fetch_list=fl,
                                return_numpy=False)
                jax.block_until_ready(o)
                compiles0 = exe.compile_count
                t0 = time.time()
                for _ in range(steps):
                    o = exe.run(m, feed=feed, fetch_list=fl,
                                return_numpy=False)
                jax.block_until_ready(o)
                dt = time.time() - t0
                return steps / dt, exe.compile_count - compiles0
        finally:
            _flags.set_flags(saved)

    default_sps, default_retraces = measure(dict(at.DEFAULT_DECISION))
    tuned_sps, tuned_retraces = measure(dict(decision))
    return {
        "decision": {k: v for k, v in decision.items() if v not in
                     (None, False, 0, "threefry") or k == "prng_impl"},
        "default_steps_per_s": round(default_sps, 3),
        "tuned_steps_per_s": round(tuned_sps, 3),
        "speedup": round(tuned_sps / max(default_sps, 1e-9), 3),
        "retraces_steady_state": int(tuned_retraces),
        "default_retraces_steady_state": int(default_retraces),
        "tune_seconds": round(tune_s, 1),
        "cache": at.cache_stats()["stats"],
    }


def _run_child(env, timeout):
    """Run this script as a measurement child; return (ok, json_line, log).

    The child runs in its own process group and is group-killed on timeout
    or parent interruption — a child left alive keeps the chip claimed."""
    import signal

    env = dict(env)
    env["_BENCH_CHILD"] = "1"
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        start_new_session=True,
    )

    def kill_group():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass

    try:
        stdout, stderr = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        kill_group()
        tail = b""
        try:
            t_out, t_err = proc.communicate(timeout=10)
            tail = (t_out or b"") + b"\n" + (t_err or b"")
        except subprocess.TimeoutExpired:
            pass
        return False, None, "child timed out after %ss: %s" % (
            timeout, tail[-2000:].decode("utf-8", "replace"))
    except BaseException:  # outer timeout/SIGTERM: never orphan the child
        kill_group()
        raise
    out = stdout.decode("utf-8", "replace")
    err = stderr.decode("utf-8", "replace")
    line = None
    for ln in out.splitlines():
        ln = ln.strip()
        if ln.startswith("{") and '"metric"' in ln:
            line = ln
    if proc.returncode == 0 and line:
        return True, line, err
    return False, None, (out + "\n" + err)[-4000:]


def main():
    if os.environ.get("_BENCH_CHILD") == "1":
        return _bench_impl()

    ok, line, log = _run_child(
        os.environ, timeout=int(os.environ.get("BENCH_TPU_TIMEOUT", "1500")))
    if not ok:
        sys.stderr.write("bench: measurement child failed:\n%s\n" % log)
        sys.exit(1)
    # distributed-mode smokes run OUTSIDE the measurement child (they
    # spawn their own CPU subprocesses); merge into the one JSON line
    if os.environ.get("BENCH_DIST", "0") == "1":
        obj = json.loads(line)
        obj["dist"] = _dist_smokes()
        line = json.dumps(obj)
    print(line)


if __name__ == "__main__":
    main()
