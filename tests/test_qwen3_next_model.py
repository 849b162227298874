"""Qwen3-Next-80B-A3B through Executor.run against
models/qwen3_next_reference.py (plain float32 jax.numpy: Gated DeltaNet as
the token-by-token recurrence in a lax.scan over T, the convolution as
shifted products, the attention as an explicit softmax under a dense mask,
rotary written out, experts as a loop over a mask) on seeded weights, at
the small widths of the benchmark configuration's `rehearse` (hidden 64, 2
key / 4 value heads of 16, 4 query / 2 KV heads of 32 with rotary on 8
lanes, T 40 so that the op pads its one chunk, four layers [GDN, GDN, GDN,
attention], 4 of the router's 16 experts held, top-3): the loss, every
token's cost and every parameter's gradient, tight in float32 and at a
written tolerance under the bf16 AMP pass; every deliberate error the
benchmark's comparison has to catch, on weights where it shows; the
sixteen shares of an expert layer and the gated shared expert counted once
add up to the uncut layer; the program verifies; it trains."""

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, framework, layers, unique_name
from paddle_tpu.models import gpt2, qwen3_next, qwen3_next_reference as ref

from expert_share import share_through_the_executor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _adapter():
    path = os.path.join(ROOT, "benchmark", "adapters", "qwen3_next_lm.py")
    spec = importlib.util.spec_from_file_location("qwen3_next_lm_adapter",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rehearsal_config():
    """benchmark/configs/qwen3_next_80b_a3b.json with its `rehearse` sizes
    laid over the published ones, as benchmark/run.py --rehearse reads
    it."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3_next_80b_a3b.json")) as f:
        data = json.load(f)
    cfg = {k: v for k, v in data.items() if k != "rehearse"}
    for k, v in data["rehearse"].items():
        cfg[k] = dict(cfg[k], **v) if isinstance(v, dict) else v
    return cfg


ADAPTER = _adapter()
ADAPTER_CFG = _rehearsal_config()
CFG = ADAPTER._arch(ADAPTER_CFG)
HP = type("HP", (qwen3_next.Qwen3NextConfig,), dict(CFG))
SEQ, BATCH = 40, 4
GDN = ["attn_norm.w", "gdn_qkv.w", "gdn_z.w", "gdn_b.w", "gdn_a.w",
       "gdn_dt.b", "gdn_conv.w", "gdn_A_log.w", "gdn_o_norm.w", "gdn_o.w",
       "ffn_norm.w"]
ATTN = ["attn_norm.w", "mha_q.w", "mha_k.w", "mha_v.w", "mha_gate.w",
        "mha_q_norm.w", "mha_k_norm.w", "mha_o.w", "ffn_norm.w"]
MOE = ["moe_router.w", "moe_gate_up.w", "moe_down.w", "shared_ffn_gate.w",
       "shared_ffn_up.w", "shared_ffn_out.w", "shared_expert_gate.w"]
ORDER = (["emb.w"] + (GDN + MOE) * 3 + ATTN + MOE
         + ["final_norm.w", "softmax_out.w"])


@functools.lru_cache(maxsize=None)
def _run(use_bf16):
    """(program loss, {param: grad}, reference loss, {param: grad}, the
    program, losses of three training steps, tokens-per-expert of the
    first expert layer, the startup weights) on seeded weights."""
    main, startup, _, fetches = qwen3_next.qwen3_next_lm_program(
        HP, seq_len=SEQ, lr=1e-3, use_bf16=use_bf16)
    startup.random_seed = main.random_seed = 5
    batch = gpt2.make_fake_lm_batch(BATCH, SEQ, HP, seed=1)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        every = main.global_block().all_parameters()
        values = [np.asarray(scope.find_var(p.name)) for p in every]
        want_loss, want_grads = ref.loss_and_grads(CFG, values, batch)
        trained = [p.name for p in every if p.trainable]
        out = exe.run(main, feed=batch, fetch_list=[fetches[0]] + [
            main._grad_names[n] for n in trained])
        steps = [float(np.asarray(out[0]).reshape(-1)[0])] + [
            float(np.asarray(exe.run(
                main, feed=batch, fetch_list=[fetches[0]])[0]).reshape(-1)[0])
            for _ in range(2)]
        counts = np.asarray(scope.find_var("moe_tokens_per_expert_0"))
    want = {p.name: g for p, g in zip(every, want_grads)}
    return (steps[0], dict(zip(trained, out[1:])), float(want_loss), want,
            main, steps, counts, [(p.name, v) for p, v in zip(every, values)])


def test_the_published_config_is_the_class_default():
    hp = qwen3_next.Qwen3NextConfig
    assert (hp.num_hidden_layers, hp.hidden_size, hp.vocab_size,
            hp.full_attention_interval) == (48, 2048, 151936, 4)
    assert (hp.linear_num_key_heads, hp.linear_num_value_heads,
            hp.linear_key_head_dim, hp.linear_value_head_dim,
            hp.linear_conv_kernel_dim) == (16, 32, 128, 128, 4)
    assert (hp.num_attention_heads, hp.num_key_value_heads, hp.head_dim,
            hp.partial_rotary_factor, hp.rope_theta) == (16, 2, 256, 0.25,
                                                         1e7)
    assert (hp.num_experts, hp.num_experts_per_tok, hp.norm_topk_prob,
            hp.moe_intermediate_size, hp.shared_expert_intermediate_size,
            hp.decoder_sparse_step, tuple(hp.mlp_only_layers)) == (
                512, 10, True, 512, 512, 1, ())
    assert hp.rms_norm_eps == 1e-6 and not hp.tie_word_embeddings
    assert [qwen3_next.mixer_of(hp, i) for i in range(8)] == [
        "gdn", "gdn", "gdn", "attn"] * 2
    assert qwen3_next.mixer_of(hp, 47) == "attn"
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3_next_80b_a3b.json")) as f:
        published = json.load(f)
    for key in ("hidden_size", "linear_num_key_heads",
                "linear_num_value_heads", "linear_key_head_dim",
                "linear_value_head_dim", "linear_conv_kernel_dim",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "partial_rotary_factor", "rope_theta", "num_experts_per_tok",
                "moe_intermediate_size", "shared_expert_intermediate_size",
                "full_attention_interval", "rms_norm_eps"):
        assert published[key] == getattr(hp, key), key
    assert published["share"]["router_experts"] == hp.num_experts


def test_the_rehearsal_keeps_what_makes_the_model():
    """Key heads shared by value heads, heads that are not hidden / heads
    wide, a length the op pads, both kinds of mixer three to one, rotary on
    a part of the head, grouped queries, a share that does not start at
    expert 0."""
    assert HP.linear_num_value_heads == 2 * HP.linear_num_key_heads
    assert HP.num_attention_heads * HP.head_dim != HP.hidden_size
    assert SEQ % 64 and HP.linear_conv_kernel_dim == 4
    assert [qwen3_next.mixer_of(HP, i) for i in range(4)] == [
        "gdn", "gdn", "gdn", "attn"]
    assert int(HP.head_dim * HP.partial_rotary_factor) == 8 < HP.head_dim
    assert HP.num_key_value_heads < HP.num_attention_heads
    assert HP.num_local_experts < HP.num_experts and HP.expert_offset
    assert (HP.num_experts, HP.num_experts_per_tok,
            HP.num_local_experts, HP.expert_offset) == (16, 3, 4, 4)


def test_every_parameter_is_created_in_the_references_order():
    block = _run(False)[4].global_block()
    names = [p.name for p in block.all_parameters()]
    assert [n.rsplit("_", 1)[0] for n in names] == ORDER
    shapes = {n: tuple(block.var(n).shape) for n in names}
    # q and k at 2 key heads of 16, v at 4 value heads of 16, side by side
    assert shapes["gdn_qkv.w_0"] == (64, 2 * 32 + 64)
    assert shapes["gdn_z.w_0"] == (64, 64)
    assert shapes["gdn_b.w_0"] == shapes["gdn_a.w_0"] == (64, 4)
    # ONE number a value head: the decay's bias, A_log
    assert shapes["gdn_dt.b_0"] == shapes["gdn_A_log.w_0"] == (4,)
    assert shapes["gdn_conv.w_0"] == (128, 4)  # one convolution, 4 taps
    assert shapes["gdn_o_norm.w_0"] == (16,) and shapes["gdn_o.w_0"] == (
        64, 64)
    assert shapes["mha_q.w_0"] == shapes["mha_gate.w_0"] == (64, 4 * 32)
    assert shapes["mha_k.w_0"] == shapes["mha_v.w_0"] == (64, 2 * 32)
    assert shapes["mha_q_norm.w_0"] == shapes["mha_k_norm.w_0"] == (32,)
    assert shapes["moe_router.w_0"] == (64, 16)  # the router's full width
    assert shapes["moe_gate_up.w_0"] == (4, 64, 64)  # four experts held
    assert shapes["shared_ffn_gate.w_0"] == (64, 32)
    assert shapes["shared_expert_gate.w_0"] == (64, 1)  # a number a token
    assert shapes["softmax_out.w_0"] == (64, 1024)  # the head is its own


def test_every_gain_starts_where_the_published_model_starts_it():
    """Every 1 + w gain's w is zero, the GDN norm's plain gain one; A_log =
    log of uniform(1, 16) and dt_bias = softplus^-1 of a log-uniform(0.001,
    0.1) draw, a head, from the seed and trained."""
    params = dict(_run(False)[7])
    for name, value in params.items():
        base = name.rsplit("_", 1)[0]
        if base in ("attn_norm.w", "ffn_norm.w", "final_norm.w",
                    "mha_q_norm.w", "mha_k_norm.w"):
            assert not value.any(), name
        if base == "gdn_o_norm.w":
            assert (value == 1.0).all(), name
    for i in range(3):
        a = np.exp(params["gdn_A_log.w_%d" % i])
        dt = np.log1p(np.exp(params["gdn_dt.b_%d" % i]))
        assert ((1.0 <= a) & (a <= 16.0)).all()
        assert ((0.000999 <= dt) & (dt <= 0.1001)).all()
    assert not np.allclose(params["gdn_dt.b_0"], params["gdn_dt.b_1"])
    block = _run(False)[4].global_block()
    assert block.var("gdn_A_log.w_0").trainable
    assert block.var("gdn_dt.b_0").trainable


def test_the_router_has_no_bias_and_the_step_no_balancing_op():
    block = _run(False)[4].global_block()
    types = [op.type for op in block.ops]
    assert "expert_bias_update" not in types
    for op in block.ops:
        if op.type == "moe_ffn":
            assert not op.inputs.get("ExpertBias")
            assert op.attrs.get("router", "softmax") == "softmax"
            assert op.attrs["top_k"] == 3 and op.attrs["norm_topk_prob"]
            assert op.attrs["expert_offset"] == 4


def test_float32_loss_matches_the_reference():
    got, _, want, _, _, _, _, _ = _run(False)
    assert abs(got - want) <= 1e-5 * abs(want), (got, want)


TRAINED = list(dict.fromkeys(ORDER))


@pytest.mark.parametrize("base", TRAINED)
def test_float32_gradient_matches_the_reference(base):
    """Every parameter of that kind, in every layer: the chunkwise op and
    its own backward (the sum over a key head's readers with it) against
    autodiff of the recurrence, the 1 + w gains through their `scale` op,
    rotary on a part of the head against the written-out rotation: 1e-4
    of the gradient's largest element."""
    _, got, _, want, _, _, _, _ = _run(False)
    names = [n for n in got if n.rsplit("_", 1)[0] == base]
    assert names
    for name in names:
        g, w = np.asarray(got[name]), np.asarray(want[name])
        assert g.shape == w.shape
        assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max(), name


def test_bf16_amp_loss_matches_the_reference_within_its_tolerance():
    got, _, want, _, _, _, _, _ = _run(True)
    assert abs(got - want) <= 2e-3, (got, want)


@pytest.mark.parametrize("use_bf16", [False, True], ids=["f32", "bf16_amp"])
def test_program_verifies_and_trains(use_bf16):
    _, _, _, _, main, steps, counts, _ = _run(use_bf16)
    diags = analysis.verify_program(main)
    assert not [d for d in diags if d.is_error], diags
    assert steps[2] < steps[1] < steps[0], steps
    # the router's decisions over all 16 experts, held here or not
    assert counts.shape == (16,)
    assert counts.sum() == BATCH * SEQ * HP.num_experts_per_tok
    types = [op.type for op in main.global_block().ops]
    assert types.count("gated_delta_attention") == 3
    assert types.count("gated_delta_attention_grad") == 3
    assert types.count("kda_attention") == 0
    assert types.count("causal_conv") == 3  # ONE convolution a GDN layer
    assert types.count("fused_attention") == 1 and types.count("moe_ffn") == 4
    assert types.count("fused_swiglu") == 4  # the shared experts
    assert types.count("fused_linear_xent") == 1


def test_each_kind_of_layer_builds_its_own_mixer():
    """A GDN layer: the projections under gdn/proj, one causal_conv with
    SiLU, the split and two L2 norms under gdn/conv, softplus / exp /
    sigmoid under gdn/gate, ONE gated_delta_attention op under gdn/core
    fed 2 key heads and 4 value heads and a decay of [B, 4, T], the
    SiLU-gated norm under gdn/out; the attention layer under attn_full:
    one fused_attention at head width 32 under core, the rotary on 8 of 32
    lanes (split, rotary_embed, concat) under rope, the gate under
    attn_gate; the shared expert and its gate under shared_expert."""
    block = _run(False)[4].global_block()
    by_scope = {}
    for op in block.ops:
        by_scope.setdefault(op.attrs.get("op_namescope"), []).append(op)
    assert {"gdn/proj", "gdn/conv", "gdn/gate", "gdn/core", "gdn/out",
            "attn_full/core", "attn_full/rope", "attn_full/attn_gate",
            "shared_expert"} <= set(by_scope)
    convs = [op for op in by_scope["gdn/conv"] if op.type == "causal_conv"]
    assert len(convs) == 3 and {op.attrs["act"] for op in convs} == {"silu"}
    norms = [op for op in by_scope["gdn/conv"] if op.type == "l2_normalize"]
    assert len(norms) == 6 and {op.attrs["epsilon"] for op in norms} == {
        1e-6}
    assert {"softplus", "exp", "sigmoid"} <= {
        op.type for op in by_scope["gdn/gate"]}
    cores = [op for op in by_scope["gdn/core"]
             if op.type == "gated_delta_attention"]
    assert len(cores) == 3
    for op in cores:
        assert tuple(block.var(op.inputs["Q"][0]).shape)[1:] == (2, SEQ, 16)
        assert tuple(block.var(op.inputs["K"][0]).shape)[1:] == (2, SEQ, 16)
        assert tuple(block.var(op.inputs["V"][0]).shape)[1:] == (4, SEQ, 16)
        assert tuple(block.var(op.inputs["G"][0]).shape)[1:] == (4, SEQ)
        assert tuple(block.var(op.inputs["Beta"][0]).shape)[1:] == (4, SEQ)
        assert op.attrs["scale"] is None  # key_dim^-0.5, the op's own
    assert {"rms_norm", "swish", "elementwise_mul"} <= {
        op.type for op in by_scope["gdn/out"]}
    rope = [op.type for op in by_scope["attn_full/rope"]
            if not op.type.endswith("_grad")]
    assert rope == ["split", "rotary_embed", "concat"] * 2
    for op in by_scope["attn_full/rope"]:
        if op.type == "rotary_embed":
            assert tuple(block.var(op.inputs["X"][0]).shape)[-1] == 8
            assert op.attrs["base"] == 1e7
    (core,) = [op for op in by_scope["attn_full/core"]
               if op.type == "fused_attention"]
    assert core.attrs["causal"] and core.attrs["scale"] == 32 ** -0.5
    assert tuple(block.var(core.inputs["Q"][0]).shape)[1:] == (4, SEQ, 32)
    assert {"sigmoid", "elementwise_mul"} <= {
        op.type for op in by_scope["attn_full/attn_gate"]}
    assert {"fused_swiglu", "sigmoid", "elementwise_mul"} <= {
        op.type for op in by_scope["shared_expert"]}


def test_rotary_on_the_whole_head_builds_what_it_built():
    """`rotary_dim` None, or the head's width, adds no op: Trinity's and
    LFM2's layers are built as before, op for op; where `scopes` is set the
    one `rotary_embed` of q and of k stands under `rope` (since PR 65: the
    name alone), as a turn of a part of the head does with its split and
    its concatenation."""
    from paddle_tpu.models import transformer as tfm

    def ops(**kwargs):
        main, startup = fluid.Program(), fluid.Program()
        with framework.program_guard(main, startup), unique_name.guard():
            x = layers.data("x", shape=[SEQ, 64], dtype="float32")
            tfm.multi_head_attention(
                x, x, x, None, 64, 4, fused=True, causal=True, n_kv_head=2,
                rotary=True, qk_norm="head", head_dim=32, out_gate=True,
                scopes=True, **kwargs)
        return [(op.type, op.attrs.get("op_namescope"))
                for op in main.global_block().ops]

    plain = ops()
    assert ops(rotary_dim=32) == plain
    assert [t for t, s in plain if s == "rope"] == ["rotary_embed"] * 2
    assert "scale" not in [t for t, _ in plain]  # the gains are plain w
    part = ops(rotary_dim=8, norm_unit_offset=True)
    assert [t for t, s in part if s == "rope"] == [
        "split", "rotary_embed", "concat"] * 2
    assert [t for t, _ in part].count("scale") == 2  # 1 + w for q and k
    with pytest.raises(ValueError, match="rotary_dim"):
        ops(rotary_dim=48)
    with pytest.raises(ValueError, match="rotary_dim"):
        ops(rotary_dim=7)


def test_program_flops_counts_every_grad_op_twice_its_forward():
    from paddle_tpu.utils.flops import program_flops

    forward, _, _, _ = qwen3_next.qwen3_next_lm_program(HP, seq_len=SEQ,
                                                        is_test=True)
    got = program_flops(forward, batch_hint=BATCH)
    assert program_flops(_run(False)[4], batch_hint=BATCH) == 3.0 * got
    # a token a VALUE head (4), not a key head (2)
    one_core = BATCH * 4 * SEQ * (2.0 * 64 * 5 * 16 + 6.0 * 16 * 16)
    assert got > 3 * one_core


@pytest.mark.parametrize("key, value, error", [
    ("decoder_sparse_step", 2, NotImplementedError),
    ("mlp_only_layers", (1,), NotImplementedError),
    ("rope_scaling", {"type": "yarn"}, NotImplementedError),
    ("use_sliding_window", True, NotImplementedError),
    ("hidden_act", "gelu", NotImplementedError),
    ("tie_word_embeddings", True, NotImplementedError),
    ("linear_num_key_heads", 3, ValueError)])
def test_what_the_builder_would_have_to_guess_it_refuses(key, value, error):
    hp = type("Guess", (HP,), {key: value})
    with pytest.raises(error):
        qwen3_next.qwen3_next_lm_program(hp, seq_len=SEQ)


# --- the departures ---------------------------------------------------------
# Weights where every departure shows.  At the startup's normal(0, 0.02) the
# convolution's output is ~0.01 (v nearly nothing), the gates', beta's and
# the decay's arguments ~0.1 (a sigmoid gate a constant 0.5, SiLU of z and
# sigmoid of z times z/2 the same to first order), every 1 + w gain exactly
# 1 and a plain w gain exactly 0 (everything or nothing), the attention's
# scores uniform (rotary moves nothing), the router's probabilities all
# ~1/16 (renormalising the top-3 is then one constant factor), and the
# logits ~0, the loss log(vocabulary) whatever the trunk computes.  Gains
# away from zero (w = 0.3 + noise: a plain-w reading is then a third of the
# signal, not none of it), 20 x filters, 30 x gate, decay and beta
# projections, a 6 x router (at 30 x the top-3 hold all the probability and
# renormalising them changes nothing), a 30 x query and key, larger value /
# output / routed / shared projections, a 6 x embedding and a 10 x head
# make each matter.
SHOW = {"emb.w": 6.0, "gdn_qkv.w": 4.0, "gdn_conv.w": 20.0,
        "gdn_z.w": 30.0, "gdn_a.w": 30.0, "gdn_b.w": 30.0, "gdn_o.w": 2.0,
        "mha_q.w": 30.0, "mha_k.w": 30.0, "mha_v.w": 10.0,
        "mha_gate.w": 30.0, "mha_o.w": 14.0, "moe_router.w": 6.0,
        "moe_down.w": 25.0, "moe_gate_up.w": 2.0, "shared_ffn_up.w": 3.0,
        "shared_ffn_out.w": 20.0, "shared_expert_gate.w": 60.0,
        "softmax_out.w": 10.0}
GAINS = ("attn_norm.w", "ffn_norm.w", "final_norm.w", "mha_q_norm.w",
         "mha_k_norm.w")


def _show_weights():
    rng = np.random.RandomState(3)
    out = []
    for name, value in _run(False)[7]:
        base = name.rsplit("_", 1)[0]
        if base in GAINS:  # zero at the startup: 1 + w and w differ by all
            value = (0.3 + 0.2 * rng.randn(*value.shape)).astype("float32")
        out.append((name, value * SHOW.get(base, 1.0)))
    return out


@functools.lru_cache(maxsize=None)
def _eval_loss_and_references():
    """The dropout-free forward loss of the program on the SHOW weights,
    the adapter's reference on the same weights (exact, with each of its
    deliberate errors, and all in bfloat16), compared as the harness
    compares them (inside the scope the forward-only program ran in, so
    the adapter pairs the program's rows with the reference's), and the
    model's reference: (program loss, {name: reference loss}, the model's
    reference's loss and rows, {name: paired readings}, the program's
    rows)."""
    params = _show_weights()
    fwd, _, _, fetches = qwen3_next.qwen3_next_lm_program(HP, seq_len=SEQ,
                                                          is_test=True)
    batch = gpt2.make_fake_lm_batch(BATCH, SEQ, HP, seed=1)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    refs, found = {}, {}
    with fluid.scope_guard(scope):
        for name, value in params:
            scope.set(name, jnp.asarray(value))
        got = float(np.asarray(exe.run(
            fwd, feed=batch, fetch_list=[fetches[0]])[0]).reshape(-1)[0])
        rows = ADAPTER.program_rows()
        for name, departure, dtype in (
                [(d, d, "float32") for d in (None,) + ADAPTER.DEPARTURES]
                + [("all_bfloat16", None, "bfloat16")]):
            _, refs[name], found[name] = ADAPTER.compare(
                ADAPTER_CFG, params, batch, departure, dtype)
    weights = [jnp.asarray(v) for _, v in params]
    with jax.default_matmul_precision("highest"):
        want = (float(ref.loss(CFG, weights, batch)),
                np.asarray(ref.token_costs(CFG, weights, batch)))
    return got, refs, want, found, rows


def test_the_adapters_reference_is_the_models_reference():
    """Two statements of the same equations, written apart (the adapter's
    attention goes one head at a time), and both with Gated DeltaNet as
    the recurrence: the same loss (float32, 1e-6), and the program's,
    whose core is the chunkwise op; every token's cost as well."""
    got, refs, (want, want_rows), _, rows = _eval_loss_and_references()
    assert refs[None] == pytest.approx(want, rel=1e-6)
    assert got == pytest.approx(want, rel=1e-5)
    np.testing.assert_allclose(rows, want_rows, rtol=1e-4, atol=1e-5)


def test_the_twelve_departures_are_the_issues():
    assert ADAPTER.DEPARTURES == (
        "no_decay", "no_beta", "no_delta_correction", "no_qk_l2norm",
        "key_head_mod", "conv_one_ahead", "gdn_gate_sigmoid",
        "plain_norm_gain", "rope_on_whole_head", "no_attn_out_gate",
        "no_shared_gate", "no_topk_renorm")


@pytest.mark.parametrize("departure", ADAPTER.DEPARTURES)
def test_each_departure_moves_the_loss_where_the_exact_reference_does_not(
        departure):
    """The program against the reference with ONE deliberate error, on
    the SHOW weights, in float32: each moves the loss by a thousand times
    what the exact reference differs by, and the cell's comparison fails
    it: the loss is outside the adapter's TOLERANCE or the paired costs
    are over their limit."""
    got, refs, _, found, _ = _eval_loss_and_references()
    exact = abs(got - refs[None])
    assert exact <= 1e-5  # six float32 steps of a loss of 8
    moved = abs(got - refs[departure])
    assert moved > 1000 * max(exact, 1e-6), (departure, got,
                                              refs[departure])
    assert (moved > ADAPTER.TOLERANCE
            or found[departure]["cost_rms_over_bf16"]
            > ADAPTER.LIMITS["cost_rms_over_bf16"]), (departure, moved,
                                                      found[departure])


def test_an_all_bfloat16_reference_is_told_from_the_exact_one():
    """A float32 program is the exact reference's to 1e-5 of the unit and
    reads the all-bfloat16 one at its own unit, 1, which is over the
    limit."""
    got, refs, _, found, _ = _eval_loss_and_references()
    assert abs(got - refs["all_bfloat16"]) > 1000 * max(
        abs(got - refs[None]), 1e-6)
    assert found[None]["cost_rms_over_bf16"] < 0.01
    assert found["all_bfloat16"]["cost_rms_over_bf16"] == pytest.approx(
        1.0, abs=1e-3)
    assert ADAPTER.LIMITS["cost_rms_over_bf16"] < 0.99


def test_the_forward_only_program_leaves_what_the_comparison_pairs():
    """Every token's cost stays in the scope of an `is_test` program; in
    float32 the rows (costs of 5 to 15 on these weights) are the exact
    reference's to 1e-4 (4.3e-5 measured: the chunkwise sums against the
    recurrence's order)."""
    found = _eval_loss_and_references()[3][None]
    assert found["cost_rms"] <= 1e-4
    train = _run(False)[4]
    assert qwen3_next.EVAL_ROWS not in train.global_block().vars


@pytest.mark.parametrize("departure",
                         ADAPTER.DEPARTURES + ("all_bfloat16",))
def test_each_departure_moves_the_paired_costs(departure):
    """Token by token nothing averages away: on the SHOW weights each
    wrong reference, and the exact one a precision down, differs from the
    program's rows by more than a thousand times what the exact one
    does, and reads over the comparison's limit."""
    found = _eval_loss_and_references()[3]
    assert found[departure]["cost_rms"] > max(
        1e-3, 1000 * found[None]["cost_rms"]), found[departure]
    assert found[departure]["cost_rms_over_bf16"] > ADAPTER.LIMITS[
        "cost_rms_over_bf16"], found[departure]


def test_a_paired_reading_over_its_limit_reaches_the_harness_as_nan(
        monkeypatch):
    """loops/train.py takes one float: a reading over its limit makes it
    NaN, which no tolerance admits; without a program's rows in the scope
    the loss comes back as it is."""
    params = _show_weights()
    batch = gpt2.make_fake_lm_batch(BATCH, SEQ, HP, seed=1)
    fwd, _, _, fetches = qwen3_next.qwen3_next_lm_program(HP, seq_len=SEQ,
                                                          is_test=True)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        assert ADAPTER.program_rows() is None
        plain = ADAPTER.reference_loss(ADAPTER_CFG, params, batch)
        for name, value in params:
            scope.set(name, jnp.asarray(value))
        exe.run(fwd, feed=batch, fetch_list=[fetches[0]])
        assert ADAPTER.reference_loss(ADAPTER_CFG, params, batch) == plain
        assert np.isnan(ADAPTER.reference_loss(
            ADAPTER_CFG, params, batch, "no_attn_out_gate"))
        assert np.isnan(ADAPTER.reference_loss(
            ADAPTER_CFG, params, batch, None, "bfloat16"))
        monkeypatch.setattr(ADAPTER, "LIMITS", {"cost_rms": 1e-12})
        assert np.isnan(ADAPTER.reference_loss(ADAPTER_CFG, params, batch))


# --- fused_attention at head width 256 ---------------------------------------
def test_fused_attention_at_head_width_256_is_the_dense_scores():
    """The flash kernels at (256, 256), interpreted here through the op's
    TPU-placed lowering (T = 512, causal), against softmax(q k^T 256^-0.5)
    v written out, result and gradients; and the op says it takes the
    width."""
    from paddle_tpu.core.registry import get_op
    from paddle_tpu.core.trace import LowerCtx
    from paddle_tpu.ops import nn_ops

    assert (256, 256) in nn_ops._FLASH_WIDTHS
    assert nn_ops._flash_engages(LowerCtx(platform="tpu"), 8192, 8192, 256)
    assert not nn_ops._flash_engages(LowerCtx(platform="tpu"), 8192, 8192,
                                     320)
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 512, 256).astype("float32") * s)
               for s in (1.0, 1.0, 1.0))
    mix = jnp.asarray(rng.rand(1, 2, 512, 256).astype("float32"))
    attrs = {"causal": True, "scale": 256 ** -0.5}

    def op(q, k, v):
        return get_op("fused_attention").lower(
            LowerCtx(platform="tpu"), {"Q": [q], "K": [k], "V": [v]},
            attrs)["Out"][0]

    def dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 256 ** -0.5
        s = jnp.where(jnp.arange(512)[:, None] >= jnp.arange(512)[None, :],
                      s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    from paddle_tpu.ops import kernel_tuning

    kernel_tuning.reset_attribution()
    with jax.default_matmul_precision("highest"):
        got, pull = jax.vjp(op, q, k, v)
        want, pull_dense = jax.vjp(dense, q, k, v)
        hits = kernel_tuning.attribution()["pallas_hits"]
        # the record says the kernel ran, and at which width
        assert hits.get("attention", 0) >= 1
        assert hits.get("attention_qk256_v256", 0) >= 1
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        for a, b in zip(pull(mix), pull_dense(mix)):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


# --- the share test ---------------------------------------------------------
SHARES = 16


class Wide(HP):
    """One layer as sixteen chips share it: a router over 32 experts,
    top-10, two experts a chip."""
    num_experts, num_experts_per_tok = 2 * SHARES, 10


def _layer_weights():
    rng = np.random.RandomState(7)
    d, e, f = Wide.hidden_size, Wide.num_experts, Wide.moe_intermediate_size
    fs = Wide.shared_expert_intermediate_size
    return {"x": rng.randn(BATCH, SEQ, d).astype("float32"),
            "router": (rng.randn(d, e) * 0.3).astype("float32"),
            "gate_up": (rng.randn(e, d, 2 * f) * 0.2).astype("float32"),
            "down": (rng.randn(e, f, d) * 0.2).astype("float32"),
            "shared": [(rng.randn(d, fs) * 0.2).astype("float32"),
                       (rng.randn(d, fs) * 0.2).astype("float32"),
                       (rng.randn(fs, d) * 0.2).astype("float32"),
                       (rng.randn(d, 1) * 0.5).astype("float32")]}


def test_the_sixteen_shares_and_the_gated_shared_expert_once_are_the_layer():
    """Sixteen chips hold two experts each of one layer.  Each routes
    (softmax, top-10, renormalised) over all thirty-two, computes its own
    experts' part and the WHOLE shared expert behind its gate; the sixteen
    routed parts plus the gated shared expert counted ONCE are what the
    uncut reference gives for the layer (adding the sixteen outputs would
    count the shared expert sixteen times), and every chip saw the same
    routing decisions."""
    w = _layer_weights()
    cfg = dict({k: getattr(Wide, k) for k in dir(Wide)
                if not k.startswith("_")}, expert_offset=0)
    args = [jnp.asarray(w[k]) for k in ("x", "router", "gate_up", "down")]
    with jax.default_matmul_precision("highest"):
        routed, top_e = ref.routed(cfg, *args)
        shared = ref.shared_expert(args[0], *map(jnp.asarray, w["shared"]))
        ungated = ref.swiglu_mlp(args[0],
                                 *map(jnp.asarray, w["shared"][:3]))
    assert np.abs(np.asarray(shared - ungated)).max() > 0.1  # the gate counts
    want_counts = np.bincount(np.asarray(top_e).reshape(-1),
                              minlength=2 * SHARES)
    parts = [share_through_the_executor(qwen3_next._experts, Wide, w, 2 * i,
                                        2, shared_bases=MOE[3:])
             for i in range(SHARES)]
    for both, part, counts in parts:
        np.testing.assert_array_equal(counts, want_counts)
        np.testing.assert_allclose(both - part, shared, rtol=1e-4, atol=1e-4)
    assert sum(np.abs(part).max() > 0 for _, part, _ in parts) >= 12
    np.testing.assert_allclose(sum(p for _, p, _ in parts) + shared,
                               routed + shared, rtol=1e-5, atol=1e-4)
    # and one share alone is what the reference gives for that share
    with jax.default_matmul_precision("highest"):
        alone, _ = ref.routed(dict(cfg, expert_offset=10), *args[:2],
                              args[2][10:12], args[3][10:12])
    np.testing.assert_allclose(parts[5][1], alone, rtol=1e-5, atol=1e-5)
