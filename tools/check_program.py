#!/usr/bin/env python
"""Static lint over every model-builder program x pass pipeline.

The CI static-analysis lane (scripts/ci.sh) runs this before the test
lanes: each builder program (train / decode / ragged serving /
dist-transpiled / remat'd / AMP'd / fused / int8) is built, pushed
through its pass pipeline with ``FLAGS_check_program`` armed (so every
``apply_pass`` postcondition fires), and verified with
``analysis.verify_program`` — all without tracing a single op.

    python tools/check_program.py             # full matrix
    python tools/check_program.py -k gpt2     # filter by name
    python tools/check_program.py --fast      # the tier-1 sweep subset

Exit status 1 if any combination reports an error-severity diagnostic.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("FLAGS_check_program", "1")

SEQ = 8


def _fresh():
    import paddle_tpu as fluid
    from paddle_tpu import framework, unique_name

    framework.switch_main_program(fluid.Program())
    framework.switch_startup_program(fluid.Program())
    unique_name.switch()


def _tiny_tfm_hp():
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


def _tiny_gpt2_hp():
    from paddle_tpu.models import gpt2

    class G(gpt2.GPT2Config):
        vocab_size = 97
        n_ctx = 32
        d_model = 16
        n_layer = 2
        n_head = 2
        dropout = 0.1

    return G


def _mlp():
    import paddle_tpu as fluid
    from paddle_tpu import layers

    x = layers.data(name="x", shape=[8], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h = layers.fc(x, size=16, act="relu")
    p = layers.fc(h, size=1)
    loss = layers.reduce_mean(layers.square_error_cost(p, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return fluid.default_main_program(), loss


# ---------------------------------------------------------------------------
# the builder x pipeline matrix; each case returns (program, verify_kwargs)
# ---------------------------------------------------------------------------
def case_mlp_train():
    main, loss = _mlp()
    return main, {"fetches": [loss.name]}


def case_mlp_memory_optimize():
    import paddle_tpu as fluid
    from paddle_tpu import transpiler

    main, loss = _mlp()
    transpiler.apply_pass(main, "memory_optimize_pass")
    return main, {"fetches": [loss.name]}


def case_mlp_dist_trainer():
    import paddle_tpu as fluid

    main, loss = _mlp()
    t = fluid.DistributeTranspiler()
    t.transpile(0, program=main,
                pservers="127.0.0.1:6174,127.0.0.1:6175", trainers=2)
    return t.get_trainer_program(), {"fetches": [loss.name]}


def case_mlp_dist_pserver():
    import paddle_tpu as fluid

    main, _loss = _mlp()
    t = fluid.DistributeTranspiler()
    t.transpile(0, program=main,
                pservers="127.0.0.1:6174,127.0.0.1:6175", trainers=2)
    return t.get_pserver_program("127.0.0.1:6174"), {}


def case_tfm_train():
    from paddle_tpu.models import transformer as tfm

    main, _s, _f, fetches = tfm.wmt_transformer_program(
        _tiny_tfm_hp(), src_len=SEQ, trg_len=SEQ)
    return main, {"fetches": [v.name for v in fetches]}


def case_tfm_amp():
    from paddle_tpu.models import transformer as tfm

    main, _s, _f, fetches = tfm.wmt_transformer_program(
        _tiny_tfm_hp(), src_len=SEQ, trg_len=SEQ, use_bf16=True)
    return main, {"fetches": [v.name for v in fetches]}


def case_tfm_remat():
    from paddle_tpu import flags
    from paddle_tpu.models import transformer as tfm

    flags.set_flags({"hbm_budget_bytes": 200 * 1024})
    try:
        main, _s, _f, fetches = tfm.wmt_transformer_program(
            _tiny_tfm_hp(), src_len=SEQ, trg_len=SEQ)
    finally:
        flags.set_flags({"hbm_budget_bytes": 0})
    return main, {"fetches": [v.name for v in fetches]}


def case_gpt2_train():
    from paddle_tpu.models import gpt2

    main, _s, _f, fetches = gpt2.gpt2_lm_program(_tiny_gpt2_hp(), seq_len=SEQ)
    return main, {"fetches": [v.name for v in fetches]}


def case_olmoe_train_amp():
    """Routed experts, RMSNorm and QK-norm under the bf16 AMP pass."""
    from paddle_tpu.models import olmoe

    class O(olmoe.OLMoEConfig):
        vocab_size, hidden_size, intermediate_size = 64, 32, 16
        num_hidden_layers, num_attention_heads, num_key_value_heads = 2, 2, 2
        num_experts, num_experts_per_tok = 4, 2

    main, _s, _f, fetches = olmoe.olmoe_lm_program(O, seq_len=SEQ,
                                                   use_bf16=True)
    return main, {"fetches": [v.name for v in fetches]}


def case_lfm2_train_amp():
    """Short-conv and GQA layers by `layer_types`, a dense layer, a chip's
    share of sigmoid-routed experts, per-head QK-norm, a tied head, under
    the bf16 AMP pass."""
    from paddle_tpu.models import lfm2

    class L(lfm2.LFM2MoEConfig):
        vocab_size, hidden_size = 64, 32
        intermediate_size, moe_intermediate_size = 48, 16
        num_hidden_layers, num_dense_layers = 3, 1
        layer_types = ["conv", "full_attention", "conv"]
        num_attention_heads, num_key_value_heads = 2, 1
        num_experts, num_experts_per_tok = 4, 2
        num_local_experts, expert_offset = 2, 2

    main, _s, _f, fetches = lfm2.lfm2_lm_program(L, seq_len=SEQ,
                                                 use_bf16=True)
    return main, {"fetches": [v.name for v in fetches]}


def case_gpt2_decode():
    from paddle_tpu.models import gpt2

    out = gpt2.gpt2_decode_step_program(_tiny_gpt2_hp(), batch=2,
                                        t_max=16, width=1)
    return out[0], {}


def case_gpt2_ragged():
    from paddle_tpu.models import gpt2

    out = gpt2.gpt2_ragged_step_program(_tiny_gpt2_hp(), batch=2,
                                        t_max=16, width=4)
    return out[0], {}


def case_gpt2_ragged_tp():
    """The tensor-parallel serving step: the SAME ragged program
    GSPMD-stamped (annotate_spmd changes execution placement only — the
    IR must verify identically to the plain build), with the gpt2
    family rule table resolving every slot-pool persistable to its
    heads-axis spec rather than a logged replicate-fallback."""
    import jax

    from paddle_tpu.models import gpt2
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.partition_rules import (
        annotate_spmd,
        partition_rules_for,
    )

    hp = _tiny_gpt2_hp()
    main, _cs, _f, _fetch, cache_names = gpt2.gpt2_ragged_step_program(
        hp, batch=2, t_max=16, width=4)
    mesh = make_mesh({"mp": -1}, devices=jax.devices())
    rules = partition_rules_for(hp.partition_family, mp_axis="mp")
    annotate_spmd(main, mesh, rules)
    specs, _repl = rules.match_table(
        {n: (2, hp.n_head, 16, hp.d_model // hp.n_head)
         for n in cache_names})
    unruled = [n for n, s in specs.items() if len(s) == 0]
    if unruled:
        raise AssertionError(
            "slot-pool persistables fell through to replication: %s"
            % unruled)
    return main, {}


def case_bert_train():
    from paddle_tpu.models import bert

    class B(bert.BertConfig):
        vocab_size = 97
        d_model = 16
        n_layer = 2
        n_head = 2
        d_inner = 32
        max_pos = 32
        type_vocab = 2

    out = bert.bert_pretrain_program(B, seq_len=SEQ)
    return out[0], {}


def case_resnet_train():
    from paddle_tpu.models import resnet

    out = resnet.build_resnet_train_program(
        batch_size=2, image_shape=(3, 32, 32), class_dim=10, depth=50)
    return out[0], {"fetches": [
        v.name if hasattr(v, "name") else str(v) for v in out[3]]}


def _conv_bn_classifier():
    """conv+BN+relu trunk with an initialized scope — the inference
    pipeline (bn_fold / train prune / int8) needs real weight values."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    img = layers.data(name="img", shape=[3, 8, 8], dtype="float32")
    label = layers.data(name="label", shape=[1], dtype="int64")
    c = layers.conv2d(img, num_filters=4, filter_size=3, padding=1)
    bn = layers.batch_norm(c, act="relu")
    p = layers.fc(layers.flatten(bn), size=10, act="softmax")
    loss = layers.mean(layers.cross_entropy(p, label))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
    return fluid.default_main_program(), p, scope


def case_inference_pipeline():
    import paddle_tpu as fluid

    main, pred, scope = _conv_bn_classifier()
    infer = main.clone(for_test=True)
    fluid.InferenceTranspiler().transpile(
        infer, scope=scope, fetches=[pred])
    return infer, {"scope": scope, "fetches": [pred.name]}


def case_int8_pipeline():
    import paddle_tpu as fluid

    main, pred, scope = _conv_bn_classifier()
    infer = main.clone(for_test=True)
    fluid.InferenceTranspiler().transpile(
        infer, scope=scope, fetches=[pred], quantize_int8=True,
        int8_min_elems=4)
    return infer, {"scope": scope, "fetches": [pred.name]}


CASES = [
    ("mlp_train", case_mlp_train, True),
    ("mlp_memory_optimize", case_mlp_memory_optimize, True),
    ("mlp_dist_trainer", case_mlp_dist_trainer, True),
    ("mlp_dist_pserver", case_mlp_dist_pserver, True),
    ("tfm_train_fused", case_tfm_train, False),
    ("tfm_amp", case_tfm_amp, False),
    ("tfm_remat", case_tfm_remat, False),
    ("gpt2_train_fused", case_gpt2_train, False),
    ("olmoe_train_amp", case_olmoe_train_amp, False),
    ("lfm2_train_amp", case_lfm2_train_amp, False),
    ("gpt2_decode_step", case_gpt2_decode, True),
    ("gpt2_ragged_serving", case_gpt2_ragged, True),
    ("gpt2_ragged_serving_tp", case_gpt2_ragged_tp, True),
    ("bert_train_fused", case_bert_train, False),
    ("resnet_train", case_resnet_train, False),
    ("inference_bn_fold_prune", case_inference_pipeline, False),
    ("inference_weight_int8", case_int8_pipeline, False),
]


def run_matrix(pattern=None, fast=False, quiet=False):
    """Returns (n_checked, n_failed, results) where results maps case
    name -> list of error diagnostics."""
    from paddle_tpu.analysis import verify_program

    results = {}
    n_checked = n_failed = 0
    for name, builder, in_fast in CASES:
        if pattern and pattern not in name:
            continue
        if fast and not in_fast:
            continue
        _fresh()
        try:
            prog, kwargs = builder()
            diags = verify_program(prog, **kwargs)
        except Exception as e:  # build or postcondition failure
            results[name] = ["BUILD/PASS FAILURE: %s: %s"
                             % (type(e).__name__, e)]
            n_checked += 1
            n_failed += 1
            if not quiet:
                print("FAIL  %-26s %s" % (name, results[name][0]))
            continue
        errors = [d for d in diags if d.is_error]
        warnings = len(diags) - len(errors)
        results[name] = [str(d) for d in errors]
        n_checked += 1
        ops = sum(len(b.ops) for b in prog.blocks)
        if errors:
            n_failed += 1
            if not quiet:
                print("FAIL  %-26s %4d ops, %d error(s), %d warning(s)"
                      % (name, ops, len(errors), warnings))
                for d in errors[:6]:
                    print("        %s" % d)
        elif not quiet:
            print("ok    %-26s %4d ops, %d warning(s)"
                  % (name, ops, warnings))
    return n_checked, n_failed, results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-k", dest="pattern", default=None,
                    help="substring filter on case names")
    ap.add_argument("--fast", action="store_true",
                    help="tier-1 sweep subset (cheap builders only)")
    ap.add_argument("-q", dest="quiet", action="store_true")
    args = ap.parse_args(argv)

    n, failed, _results = run_matrix(args.pattern, args.fast, args.quiet)
    print("check_program: %d/%d combinations verify clean"
          % (n - failed, n))
    return 1 if failed or n == 0 else 0


if __name__ == "__main__":
    sys.exit(main())
