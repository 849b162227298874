"""Kimi-Linear-48B-A3B through Executor.run against
models/kimi_linear_reference.py (plain float32 jax.numpy: Kimi Delta
Attention as the token-by-token recurrence in a lax.scan over T, the
convolution as shifted products, latent attention as an explicit softmax
under a dense mask, experts as a loop over a mask) on seeded weights, at
the small widths of the benchmark configuration's `rehearse` (hidden 64, 2
heads of 16, T 40 so that the op pads its one chunk, three layers: a KDA
layer with the dense MLP, a KDA layer and an MLA layer with experts, 2 of
the router's 8 experts held): the loss, every token's cost and every
parameter's gradient, tight in float32 and at a written tolerance under
the bf16 AMP pass; every deliberate error the benchmark's comparison has
to catch, on weights where it shows; the thirty-two shares of an expert
layer and the shared expert counted once add up to the uncut layer; the
program verifies; it trains."""

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
from paddle_tpu.models import gpt2, kimi_linear, kimi_linear_reference as ref

from expert_share import share_through_the_executor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _adapter():
    path = os.path.join(ROOT, "benchmark", "adapters", "kimi_linear_lm.py")
    spec = importlib.util.spec_from_file_location("kimi_linear_lm_adapter",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rehearsal_config():
    """benchmark/configs/kimi_linear_48b_a3b.json with its `rehearse` sizes
    laid over the published ones, as benchmark/run.py --rehearse reads
    it."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi_linear_48b_a3b.json")) as f:
        data = json.load(f)
    cfg = {k: v for k, v in data.items() if k != "rehearse"}
    for k, v in data["rehearse"].items():
        cfg[k] = dict(cfg[k], **v) if isinstance(v, dict) else v
    return cfg


ADAPTER = _adapter()
ADAPTER_CFG = _rehearsal_config()
CFG = ADAPTER._arch(ADAPTER_CFG)
HP = type("HP", (kimi_linear.KimiLinearConfig,), dict(CFG))
SEQ, BATCH = 40, 4
KDA = ["attn_norm.w", "kda_q.w", "kda_k.w", "kda_v.w", "kda_f_a.w",
       "kda_f_b.w", "kda_dt.b", "kda_g_a.w", "kda_g_b.w", "kda_b.w",
       "kda_q_conv.w", "kda_k_conv.w", "kda_v_conv.w", "kda_A_log.w",
       "kda_o_norm.w", "kda_o.w", "ffn_norm.w"]
MLA = ["attn_norm.w", "mla_q.w", "mla_kv_a.w", "mla_kv_a_norm.w",
       "mla_kv_b.w", "mla_o.w", "ffn_norm.w"]
DENSE = ["ffn_gate.w", "ffn_up.w", "ffn_out.w"]
BIAS = "moe_e_score_correction_bias.b"
MOE = ["moe_router.w", BIAS, "moe_gate_up.w", "moe_down.w",
       "shared_ffn_gate.w", "shared_ffn_up.w", "shared_ffn_out.w"]
ORDER = (["emb.w"] + KDA + DENSE + KDA + MOE + MLA + MOE
         + ["final_norm.w", "softmax_out.w"])


@functools.lru_cache(maxsize=None)
def _run(use_bf16):
    """(program loss, {param: grad}, reference loss, {param: grad}, the
    program, losses of three training steps, tokens-per-expert of the
    first expert layer, the startup weights) on seeded weights."""
    main, startup, _, fetches = kimi_linear.kimi_linear_lm_program(
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
    hp = kimi_linear.KimiLinearConfig
    assert (hp.num_hidden_layers, hp.hidden_size, hp.num_attention_heads,
            hp.num_key_value_heads, hp.vocab_size) == (
                27, 2304, 32, 32, 163840)
    la = hp.linear_attn_config
    assert la["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert la["kda_layers"] == [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17,
                                18, 19, 21, 22, 23, 25, 26]
    assert (la["num_heads"], la["head_dim"],
            la["short_conv_kernel_size"]) == (32, 128, 4)
    assert (hp.kv_lora_rank, hp.q_lora_rank, hp.qk_nope_head_dim,
            hp.qk_rope_head_dim, hp.v_head_dim, hp.mla_use_nope) == (
                512, None, 128, 64, 128, True)
    assert (hp.num_experts, hp.num_experts_per_token, hp.num_shared_experts,
            hp.first_k_dense_replace, hp.moe_intermediate_size,
            hp.intermediate_size) == (256, 8, 1, 1, 1024, 9216)
    assert (hp.routed_scaling_factor, hp.moe_renormalize,
            hp.moe_router_activation_func, hp.num_expert_group,
            hp.topk_group, hp.rms_norm_eps) == (2.446, True, "sigmoid", 1, 1,
                                                1e-5)
    assert not hp.tie_word_embeddings
    assert [kimi_linear.mixer_of(hp, i) for i in range(8)] == [
        "kda", "kda", "kda", "mla", "kda", "kda", "kda", "mla"]
    assert kimi_linear.mixer_of(hp, 26) == "mla"


def test_the_rehearsal_keeps_what_makes_the_model():
    """Heads that are not hidden / heads wide, a length the op pads, both
    kinds of mixer, the dense layer a KDA layer, scores wider than values,
    a share."""
    la = HP.linear_attn_config
    assert la["num_heads"] * la["head_dim"] != HP.hidden_size
    assert SEQ % 64 and la["short_conv_kernel_size"] == 4
    assert [kimi_linear.mixer_of(HP, i) for i in range(3)] == [
        "kda", "kda", "mla"]
    assert HP.first_k_dense_replace == 1 and HP.mla_use_nope
    assert HP.qk_nope_head_dim + HP.qk_rope_head_dim != HP.v_head_dim
    assert HP.num_local_experts < HP.num_experts and HP.expert_offset


def test_every_parameter_is_created_in_the_references_order():
    block = _run(False)[4].global_block()
    names = [p.name for p in block.all_parameters()]
    assert [n.rsplit("_", 1)[0] for n in names] == ORDER
    shapes = {n: tuple(block.var(n).shape) for n in names}
    assert shapes["kda_q.w_0"] == shapes["kda_v.w_0"] == (64, 2 * 16)
    assert shapes["kda_f_a.w_0"] == shapes["kda_g_a.w_0"] == (64, 16)
    assert shapes["kda_f_b.w_0"] == shapes["kda_g_b.w_0"] == (16, 32)
    assert shapes["kda_dt.b_0"] == (32,) and shapes["kda_b.w_0"] == (64, 2)
    assert shapes["kda_q_conv.w_0"] == (32, 4)  # one 4-tap filter a channel
    assert shapes["kda_A_log.w_0"] == (2, 1)
    assert shapes["kda_o_norm.w_0"] == (16,) and shapes["kda_o.w_0"] == (
        32, 64)
    assert shapes["mla_q.w_0"] == (64, 2 * (16 + 8))
    assert shapes["mla_kv_a.w_0"] == (64, 32 + 8)
    assert shapes["mla_kv_b.w_0"] == (32, 2 * (16 + 16))
    assert shapes["moe_router.w_0"] == (64, 8)  # the router's full width
    assert shapes[BIAS + "_0"] == (8,)
    assert shapes["moe_gate_up.w_0"] == (2, 64, 64)  # two experts held
    assert shapes["shared_ffn_gate.w_0"] == (64, 32)
    assert shapes["softmax_out.w_0"] == (64, 256)  # the head is its own


def test_the_decay_starts_where_the_published_layer_starts_it():
    """A_log = log of uniform(1, 16) a head, dt_bias = softplus^-1 of
    exp(uniform(log 0.001, log 0.1)) a channel, both from the seed and
    trained; another seed draws others."""
    params = dict(_run(False)[7])
    for i in range(2):
        a = np.exp(params["kda_A_log.w_%d" % i])
        dt = np.log1p(np.exp(params["kda_dt.b_%d" % i]))
        assert ((1.0 <= a) & (a <= 16.0)).all()
        assert ((0.000999 <= dt) & (dt <= 0.1001)).all()
        assert dt.max() > 3 * dt.min()
    assert not np.allclose(params["kda_dt.b_0"], params["kda_dt.b_1"])
    block = _run(False)[4].global_block()
    assert block.var("kda_A_log.w_0").trainable
    assert block.var("kda_dt.b_0").trainable


def test_the_selection_bias_is_a_buffer_and_every_step_balances_it():
    """Persistable, seeded non-zero, no gradient and no optimizer state;
    one `expert_bias_update` per mixture layer after the optimizer, with
    the builder's `bias_rate` / `bias_max_step` as its attributes where
    given and none where not; not in a forward-only program."""
    main = _run(False)[4]
    block = main.global_block()
    biases = [p for p in block.all_parameters() if p.name.startswith(BIAS)]
    assert len(biases) == 2 and not any(p.trainable for p in biases)
    assert not [n for n in block.vars if BIAS in n and "moment" in n]
    updates = [op for op in block.ops if op.type == "expert_bias_update"]
    assert len(updates) == 2
    assert all("rate" not in op.attrs and "max_step" not in op.attrs
               for op in updates)
    types = [op.type for op in block.ops]
    assert types.index("expert_bias_update") > max(
        i for i, t in enumerate(types) if t == "adam")
    tuned, _, _, _ = kimi_linear.kimi_linear_lm_program(
        HP, seq_len=SEQ, bias_rate=0.03, bias_max_step=0.03)
    assert [(op.attrs["rate"], op.attrs["max_step"])
            for op in tuned.global_block().ops
            if op.type == "expert_bias_update"] == [(0.03, 0.03)] * 2
    eval_main, _, _, _ = kimi_linear.kimi_linear_lm_program(
        HP, seq_len=SEQ, is_test=True)
    assert "expert_bias_update" not in [
        op.type for op in eval_main.global_block().ops]


def test_float32_loss_matches_the_reference():
    got, _, want, _, _, _, _, _ = _run(False)
    assert abs(got - want) <= 1e-5 * abs(want), (got, want)


TRAINED = [n for n in dict.fromkeys(ORDER) if n != BIAS]


@pytest.mark.parametrize("base", TRAINED)
def test_float32_gradient_matches_the_reference(base):
    """Every parameter of that kind, in every layer: the chunkwise op and
    its own backward against autodiff of the recurrence, the convolution's
    written-out VJP against autodiff of shifted products: 1e-4 of the
    gradient's largest element."""
    _, got, _, want, _, _, _, _ = _run(False)
    names = [n for n in got if n.rsplit("_", 1)[0] == base]
    assert names
    for name in names:
        g, w = np.asarray(got[name]), np.asarray(want[name])
        assert g.shape == w.shape
        assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max(), name


def test_bf16_amp_loss_matches_the_reference_within_its_tolerance():
    """bf16 matmuls against float32 "highest": 1.4e-5 measured on a loss
    of 5.55 at these widths."""
    got, _, want, _, _, _, _, _ = _run(True)
    assert abs(got - want) <= 2e-3, (got, want)


@pytest.mark.parametrize("use_bf16", [False, True], ids=["f32", "bf16_amp"])
def test_program_verifies_and_trains(use_bf16):
    _, _, _, _, main, steps, counts, _ = _run(use_bf16)
    diags = analysis.verify_program(main)
    assert not [d for d in diags if d.is_error], diags
    assert steps[2] < steps[1] < steps[0], steps
    # the router's decisions over all 8 experts, held here or not
    assert counts.shape == (8,)
    assert counts.sum() == BATCH * SEQ * HP.num_experts_per_token
    types = [op.type for op in main.global_block().ops]
    assert types.count("kda_attention") == 2
    assert types.count("kda_attention_grad") == 2
    assert types.count("causal_conv") == 6
    assert types.count("fused_attention") == 1 and types.count("moe_ffn") == 2
    # the dense layer's MLP and the two shared experts
    assert types.count("fused_swiglu") == 3
    assert types.count("fused_linear_xent") == 1


def test_each_kind_of_layer_builds_its_own_mixer():
    """A KDA layer: the projections under kda/proj, three causal_conv ops
    with SiLU and two L2 norms under kda/conv, softplus / exp / sigmoid
    under kda/gate, ONE kda_attention op under kda/core, the gated norm
    under kda/out; the MLA layer: no rotary_embed op anywhere, q never
    split, the one shared key part expanded and concatenated under
    mla/rope, scores 24 wide over 16-wide values; the shared expert under
    shared_expert."""
    block = _run(False)[4].global_block()
    by_scope = {}
    for op in block.ops:
        by_scope.setdefault(op.attrs.get("op_namescope"), []).append(op)
    assert {"kda/proj", "kda/conv", "kda/gate", "kda/core", "kda/out",
            "mla/down", "mla/up", "mla/rope", "mla/core", "mla/out",
            "shared_expert"} <= set(by_scope)
    convs = [op for op in by_scope["kda/conv"] if op.type == "causal_conv"]
    assert len(convs) == 6 and {op.attrs["act"] for op in convs} == {"silu"}
    norms = [op for op in by_scope["kda/conv"] if op.type == "l2_normalize"]
    assert len(norms) == 4 and {op.attrs["epsilon"] for op in norms} == {
        1e-6}
    assert {"softplus", "exp", "sigmoid"} <= {
        op.type for op in by_scope["kda/gate"]}
    cores = [op for op in by_scope["kda/core"] if op.type == "kda_attention"]
    assert len(cores) == 2
    for op in cores:
        assert tuple(block.var(op.inputs["Q"][0]).shape)[1:] == (2, SEQ, 16)
        assert tuple(block.var(op.inputs["Beta"][0]).shape)[1:] == (2, SEQ)
        assert op.attrs["scale"] is None  # head_dim^-0.5, the op's own
    assert {"rms_norm", "sigmoid", "elementwise_mul"} <= {
        op.type for op in by_scope["kda/out"]}
    assert not [op for op in block.ops if op.type == "rotary_embed"]
    rope = [op.type for op in by_scope["mla/rope"]
            if not op.type.endswith("_grad")]
    assert "split" not in rope and rope.count("concat") == 1
    assert "expand" in rope
    (core,) = [op for op in by_scope["mla/core"]
               if op.type == "fused_attention"]
    assert core.attrs["causal"] and core.attrs["scale"] == 24 ** -0.5
    assert tuple(block.var(core.inputs["Q"][0]).shape)[1:] == (2, SEQ, 24)
    assert tuple(block.var(core.inputs["V"][0]).shape)[1:] == (2, SEQ, 16)
    assert "fused_swiglu" in {op.type for op in by_scope["shared_expert"]}
    for op in block.ops:
        if op.type == "moe_ffn":
            assert op.attrs["routed_scaling_factor"] == 2.446
            assert op.attrs["norm_topk_eps"] == 1e-20


def test_latent_attention_with_rotary_is_what_it_was():
    """`rotary=True` (kanana-2's path) builds the ops it built before the
    switch, in their order."""
    from paddle_tpu.models import transformer as tfm

    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = layers.data("x", shape=[SEQ, 64], dtype="float32")
        tfm.latent_attention(x, 2, 32, 16, 8, 16)
    assert [op.type for op in main.global_block().ops
            if op.attrs.get("op_namescope") == "mla/rope"] == [
        "reshape2", "transpose2", "split", "rotary_embed", "reshape2",
        "rotary_embed", "expand", "concat", "concat"]


def test_program_flops_counts_every_grad_op_twice_its_forward():
    from paddle_tpu.utils.flops import program_flops

    forward, _, _, _ = kimi_linear.kimi_linear_lm_program(HP, seq_len=SEQ,
                                                          is_test=True)
    got = program_flops(forward, batch_hint=BATCH)
    assert program_flops(_run(False)[4], batch_hint=BATCH) == 3.0 * got
    one_core = BATCH * 2 * SEQ * (2.0 * 64 * 5 * 16 + 6.0 * 16 * 16)
    no_kda = type("NoKda", (HP,), {"linear_attn_config": dict(
        HP.linear_attn_config, kda_layers=[], full_attn_layers=[1, 2, 3])})
    mla_only, _, _, _ = kimi_linear.kimi_linear_lm_program(
        no_kda, seq_len=SEQ, is_test=True)
    assert [op.type for op in mla_only.global_block().ops].count(
        "kda_attention") == 0
    assert got > 2 * one_core


@pytest.mark.parametrize("key, value, error", [
    ("num_expert_group", 2, NotImplementedError),
    ("topk_group", 2, NotImplementedError),
    ("moe_router_activation_func", "softmax", NotImplementedError),
    ("q_lora_rank", 1536, NotImplementedError),
    ("rope_scaling", {"type": "yarn"}, NotImplementedError),
    ("moe_layer_freq", 2, NotImplementedError),
    ("tie_word_embeddings", True, NotImplementedError),
    ("num_key_value_heads", 1, ValueError),
    ("linear_attn_config", dict(HP.linear_attn_config, kda_layers=[1]),
     ValueError),
    ("linear_attn_config", dict(HP.linear_attn_config,
                                full_attn_layers=[2, 3]), ValueError)])
def test_what_the_builder_would_have_to_guess_it_refuses(key, value, error):
    hp = type("Guess", (HP,), {key: value})
    with pytest.raises(error):
        kimi_linear.kimi_linear_lm_program(hp, seq_len=SEQ)


# --- the departures ---------------------------------------------------------
# Weights where every departure shows.  At the startup's normal(0, 0.02) the
# convolutions' outputs are ~0.01 (v nearly nothing), the gates' and beta's
# arguments ~0.1 and their sigmoids a constant 0.5, the decay's low-rank
# part nothing beside dt_bias (every channel of a head then differs by
# dt_bias alone), the latent attention's scores uniform (rotary moves
# nothing), the router's scores all ~0.5, and the logits ~0, the loss
# log(vocabulary) whatever the trunk computes.  20 x filters, 30 x decay,
# gate, beta and router projections, a 30 x latent query, larger value /
# output / routed projections, an 8 x embedding and a 15 x head make each
# matter.
SHOW = {"emb.w": 8.0, "kda_v.w": 4.0, "kda_q_conv.w": 20.0,
        "kda_k_conv.w": 20.0, "kda_v_conv.w": 20.0, "kda_g_b.w": 30.0,
        "kda_f_b.w": 30.0, "kda_b.w": 30.0, "kda_o.w": 4.0,
        "mla_q.w": 30.0, "mla_kv_a.w": 10.0, "mla_kv_b.w": 4.0,
        "mla_o.w": 4.0, "moe_router.w": 30.0, "moe_down.w": 12.0,
        "moe_gate_up.w": 2.0, BIAS: 3.0, "softmax_out.w": 15.0}


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
    params = [(n, v * SHOW.get(n.rsplit("_", 1)[0], 1.0))
              for n, v in _run(False)[7]]
    fwd, _, _, fetches = kimi_linear.kimi_linear_lm_program(HP, seq_len=SEQ,
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
    latent attention goes one head at a time), and both with KDA as the
    recurrence: the same loss (float32, 1e-6), and the program's, whose
    KDA is the chunkwise op; every token's cost as well."""
    got, refs, (want, want_rows), _, rows = _eval_loss_and_references()
    assert refs[None] == pytest.approx(want, rel=1e-6)
    assert got == pytest.approx(want, rel=1e-5)
    np.testing.assert_allclose(rows, want_rows, rtol=1e-4, atol=1e-5)


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
    assert exact <= 5e-6
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
    float32 the rows are the exact reference's to 1e-5."""
    found = _eval_loss_and_references()[3][None]
    assert found["cost_rms"] <= 1e-5
    train = _run(False)[4]
    assert kimi_linear.EVAL_ROWS not in train.global_block().vars


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
    params = [(n, v) for n, v in _run(False)[7]]
    batch = gpt2.make_fake_lm_batch(BATCH, SEQ, HP, seed=1)
    fwd, _, _, fetches = kimi_linear.kimi_linear_lm_program(HP, seq_len=SEQ,
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
            ADAPTER_CFG, params, batch, "no_out_gate"))
        assert np.isnan(ADAPTER.reference_loss(
            ADAPTER_CFG, params, batch, None, "bfloat16"))
        monkeypatch.setattr(ADAPTER, "LIMITS", {"cost_rms": 1e-12})
        assert np.isnan(ADAPTER.reference_loss(ADAPTER_CFG, params, batch))


# --- the share test ---------------------------------------------------------
SHARES = 32


class Wide(HP):
    """One layer as thirty-two chips share it: a router over 32 experts,
    top-8, one expert a chip."""
    num_experts, num_experts_per_token = SHARES, 8


def _layer_weights():
    rng = np.random.RandomState(7)
    d, e, f = Wide.hidden_size, Wide.num_experts, Wide.moe_intermediate_size
    fs = Wide.num_shared_experts * f
    return {"x": rng.randn(BATCH, SEQ, d).astype("float32"),
            "router": (rng.randn(d, e) * 0.3).astype("float32"),
            "bias": (rng.randn(e) * 0.3).astype("float32"),
            "gate_up": (rng.randn(e, d, 2 * f) * 0.2).astype("float32"),
            "down": (rng.randn(e, f, d) * 0.2).astype("float32"),
            "shared": [(rng.randn(d, fs) * 0.2).astype("float32"),
                       (rng.randn(d, fs) * 0.2).astype("float32"),
                       (rng.randn(fs, d) * 0.2).astype("float32")]}


def test_the_thirty_two_shares_and_the_shared_expert_once_are_the_layer():
    """Thirty-two chips hold one expert each of one layer.  Each routes
    over all thirty-two, computes its own expert's part and the WHOLE
    shared expert; the thirty-two routed parts plus the shared expert
    counted ONCE are what the uncut reference gives for the layer (adding
    the thirty-two outputs would count the shared expert thirty-two
    times), and every chip saw the same routing decisions."""
    w = _layer_weights()
    cfg = dict({k: getattr(Wide, k) for k in dir(Wide)
                if not k.startswith("_")}, expert_offset=0)
    args = [jnp.asarray(w[k]) for k in ("x", "router", "bias", "gate_up",
                                        "down")]
    with jax.default_matmul_precision("highest"):
        routed, top_e = ref.routed(cfg, *args)
        shared = ref.swiglu_mlp(args[0], *map(jnp.asarray, w["shared"]))
    want_counts = np.bincount(np.asarray(top_e).reshape(-1),
                              minlength=SHARES)
    parts = [share_through_the_executor(kimi_linear._experts, Wide, w,
                                        offset, 1)
             for offset in range(SHARES)]
    for both, part, counts in parts:
        np.testing.assert_array_equal(counts, want_counts)
        np.testing.assert_allclose(both - part, shared, rtol=1e-4, atol=1e-4)
    assert sum(np.abs(part).max() > 0 for _, part, _ in parts) >= 24
    np.testing.assert_allclose(sum(p for _, p, _ in parts) + shared,
                               routed + shared, rtol=1e-5, atol=1e-4)
    # and one share alone is what the reference gives for that share
    with jax.default_matmul_precision("highest"):
        alone, _ = ref.routed(dict(cfg, expert_offset=5), *args[:3],
                              args[3][5:6], args[4][5:6])
    np.testing.assert_allclose(parts[5][1], alone, rtol=1e-5, atol=1e-5)
