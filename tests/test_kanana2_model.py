"""kanana-2-30b-a3b through Executor.run against models/kanana2_reference.py
(plain float32 jax.numpy: latent attention as an explicit masked softmax
with RoPE written out on the published pairs, experts as a loop over a
mask) on seeded weights, at a tiny size that has the leading dense layer
and two expert layers with 2 of the router's 8 experts held: the loss and
every parameter's gradient, tight in float32 and at a written tolerance
under the bf16 AMP pass; every deliberate error the benchmark's comparison
has to catch, on weights where it shows; the shares of an expert layer and
the shared expert counted once add up to the uncut layer; the two rotary
pairings agree after the permutation; the program verifies; it trains."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, framework, layers, unique_name
from paddle_tpu.initializer import NumpyArrayInitializer
from paddle_tpu.models import (decoder, gpt2, kanana2,
                               kanana2_reference as ref)
from paddle_tpu.ops import moe_ops
from paddle_tpu.param_attr import ParamAttr

from expert_share import share_through_the_executor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class HP(kanana2.Kanana2Config):
    vocab_size = 256
    hidden_size = 64
    intermediate_size = 96
    moe_intermediate_size = 32
    num_hidden_layers = 3
    num_attention_heads = 2
    num_key_value_heads = 2
    kv_lora_rank = 32
    qk_nope_head_dim = 16
    qk_rope_head_dim = 8
    v_head_dim = 16
    n_routed_experts = 8
    num_experts_per_tok = 2
    num_local_experts = 2
    expert_offset = 2


CFG = {k: getattr(HP, k) for k in dir(HP) if not k.startswith("_")}
SEQ, BATCH = 32, 4
MLA = ["attn_norm.w", "mla_q.w", "mla_kv_a.w", "mla_kv_a_norm.w",
       "mla_kv_b.w", "mla_o.w"]
DENSE = ["ffn_norm.w", "ffn_gate.w", "ffn_up.w", "ffn_out.w"]
MOE = ["ffn_norm.w", "moe_router.w", "moe_e_score_correction_bias.b",
       "moe_gate_up.w", "moe_down.w", "shared_ffn_gate.w", "shared_ffn_up.w",
       "shared_ffn_out.w"]
ORDER = (["emb.w"] + MLA + DENSE + MLA + MOE + MLA + MOE
         + ["final_norm.w", "softmax_out.w"])
BIAS = "moe_e_score_correction_bias.b"


@functools.lru_cache(maxsize=None)
def _run(use_bf16):
    """(program loss, {param: grad}, reference loss, {param: grad}, the
    program, losses of three training steps, tokens-per-expert of the
    first expert layer, the startup weights) on seeded weights."""
    main, startup, _, fetches = kanana2.kanana2_lm_program(
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


def _names():
    return [p.name for p in _run(False)[4].global_block().all_parameters()]


def test_the_published_config_is_the_class_default():
    hp = kanana2.Kanana2Config
    assert (hp.num_hidden_layers, hp.hidden_size, hp.num_attention_heads,
            hp.vocab_size) == (48, 2048, 32, 128256)
    assert (hp.kv_lora_rank, hp.q_lora_rank, hp.qk_nope_head_dim,
            hp.qk_rope_head_dim, hp.v_head_dim) == (512, None, 128, 64, 128)
    assert (hp.n_routed_experts, hp.num_experts_per_tok, hp.n_shared_experts,
            hp.first_k_dense_replace, hp.moe_intermediate_size,
            hp.intermediate_size) == (128, 6, 2, 1, 768, 6144)
    assert (hp.routed_scaling_factor, hp.n_group, hp.topk_group,
            hp.scoring_func, hp.topk_method) == (
                2.448, 1, 1, "sigmoid", "noaux_tc")
    assert hp.rope_interleave and not hp.tie_word_embeddings


def test_every_parameter_is_created_in_the_references_order():
    names = _names()
    assert [n.rsplit("_", 1)[0] for n in names] == ORDER
    block = _run(False)[4].global_block()
    shapes = {n: tuple(block.var(n).shape) for n in names}
    assert shapes["mla_q.w_0"] == (64, 2 * 24)     # H x (nope + rope)
    assert shapes["mla_kv_a.w_0"] == (64, 32 + 8)  # the latent + ONE key
    assert shapes["mla_kv_a_norm.w_0"] == (32,)
    assert shapes["mla_kv_b.w_0"] == (32, 2 * 32)  # H x (nope + v)
    assert shapes["mla_o.w_0"] == (2 * 16, 64)
    assert shapes["moe_router.w_0"] == (64, 8)  # the router's full width
    assert shapes[BIAS + "_0"] == (8,)
    assert shapes["moe_gate_up.w_0"] == (2, 64, 64)  # two experts held
    assert shapes["shared_ffn_gate.w_0"] == (64, 2 * 32)  # 2 shared, as one
    assert shapes["softmax_out.w_0"] == (64, 256)  # the head is its own


def test_the_selection_bias_is_a_buffer_and_every_step_balances_it():
    """Persistable, seeded non-zero, no gradient and no optimizer state;
    one `expert_bias_update` per mixture layer after the optimizer, and
    the bias after a step is the one before it plus the builder's rate
    times (1 - c / mean(c)) over that step's counts: lfm2's op, as it
    is."""
    _, got, _, _, main, _, _, _ = _run(False)
    block = main.global_block()
    bias = block.var(BIAS + "_0")
    assert bias.persistable and not bias.trainable and BIAS + "_0" not in got
    adam = [op for op in block.ops if op.type == "adam"]
    assert len(adam) == len(block.all_parameters()) - 2
    updates = [op for op in block.ops if op.type == "expert_bias_update"]
    assert [op.inputs["ExpertBias"] for op in updates] == [
        [BIAS + "_%d" % i] for i in range(2)]
    assert block.ops.index(updates[0]) > max(
        i for i, op in enumerate(block.ops) if op.type == "adam")
    fresh, startup, _, fetches = kanana2.kanana2_lm_program(
        HP, seq_len=SEQ, lr=1e-3)
    startup.random_seed = fresh.random_seed = 5
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        before = np.asarray(scope.find_var(BIAS + "_1"))
        assert np.abs(before).max() > 0.01
        exe.run(fresh, feed=gpt2.make_fake_lm_batch(BATCH, SEQ, HP, seed=1),
                fetch_list=[fetches[0]])
        counts = np.asarray(scope.find_var(
            "moe_tokens_per_expert_1")).astype("float32")
        np.testing.assert_allclose(
            np.asarray(scope.find_var(BIAS + "_1")),
            before + moe_ops.EXPERT_BIAS_RATE * (
                1.0 - counts / counts.mean()), atol=1e-6)
    eval_main, _, _, _ = kanana2.kanana2_lm_program(HP, seq_len=SEQ,
                                                    is_test=True)
    assert "expert_bias_update" not in [
        op.type for op in eval_main.global_block().ops]


def test_float32_loss_matches_the_reference():
    got, _, want, _, _, _, _, _ = _run(False)
    assert abs(got - want) <= 1e-5 * abs(want), (got, want)


TRAINED = [n for n in dict.fromkeys(ORDER) if n != BIAS]


@pytest.mark.parametrize("base", TRAINED)
def test_float32_gradient_matches_the_reference(base):
    """Every parameter of that kind, in every layer: the same arithmetic
    in another order, 1e-4 of the gradient's largest element (measured:
    7e-7 or less)."""
    _, got, _, want, _, _, _, _ = _run(False)
    names = [n for n in got if n.rsplit("_", 1)[0] == base]
    assert names
    for name in names:
        g, w = np.asarray(got[name]), np.asarray(want[name])
        assert g.shape == w.shape
        assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max(), name


def test_bf16_amp_loss_matches_the_reference_within_its_tolerance():
    """bf16 matmuls against float32 "highest": 2.6e-5 measured on a loss
    of 5.53 at these widths; benchmark/adapters/kanana2_lm.py allows the
    same recipe 3e-3 at the published ones."""
    got, _, want, _, _, _, _, _ = _run(True)
    assert abs(got - want) <= 2e-3, (got, want)


@pytest.mark.parametrize("name, within", [
    ("emb.w_0", 0.06), ("mla_q.w_0", 0.05), ("mla_kv_a.w_0", 0.05),
    ("mla_kv_a_norm.w_1", 0.05), ("mla_kv_b.w_1", 0.05),
    ("mla_o.w_2", 0.05), ("shared_ffn_out.w_0", 0.05),
    ("moe_router.w_0", 0.05), ("moe_down.w_0", 0.05),
    ("moe_down.w_1", 0.4)])
def test_bf16_amp_gradient_is_close_to_the_reference(name, within):
    """bf16 rounding of every activation: 6% of the gradient's largest
    element (measured: 3.6% or less).  The last expert layer is where
    bf16 flipped a routing decision at this seed: a top-k is
    discontinuous, and that layer's gradients differ by 14 to 27% (the
    discontinuity the adapter's TOLERANCE speaks of)."""
    _, got, _, want, _, _, _, _ = _run(True)
    g, w = np.asarray(got[name]), np.asarray(want[name])
    assert np.abs(g - w).max() <= within * np.abs(w).max(), name


@pytest.mark.parametrize("use_bf16", [False, True], ids=["f32", "bf16_amp"])
def test_program_verifies_and_trains(use_bf16):
    _, _, _, _, main, steps, counts, _ = _run(use_bf16)
    diags = analysis.verify_program(main)
    assert not [d for d in diags if d.is_error], diags
    assert steps[2] < steps[1] < steps[0], steps
    # the router's decisions over all 8 experts, held here or not
    assert counts.shape == (8,)
    assert counts.sum() == BATCH * SEQ * HP.num_experts_per_tok
    types = [op.type for op in main.global_block().ops]
    assert types.count("fused_attention") == 3 and types.count("moe_ffn") == 2
    # the dense layer's MLP and the two shared experts
    assert types.count("fused_swiglu") == 3
    assert types.count("fused_linear_xent") == 1


def test_the_ops_carry_their_name_scopes_and_latent_widths():
    """Every op of a latent-attention layer is under mla > down | up |
    rope | core | out, the shared expert under shared_expert; the core's V
    is of another width than its Q and K, and its result of V's."""
    main = _run(False)[4]
    block = main.global_block()
    scopes = {}
    for op in block.ops:
        scopes.setdefault(op.attrs.get("op_namescope"), set()).add(op.type)
    assert {"mla/down", "mla/up", "mla/rope", "mla/core", "mla/out",
            "shared_expert"} <= set(scopes)
    assert scopes["mla/core"] == {"fused_attention", "fused_attention_grad"}
    assert "rotary_embed" in scopes["mla/rope"]
    assert "rms_norm" in scopes["mla/down"]
    assert "fused_swiglu" in scopes["shared_expert"]
    for op in block.ops:
        if op.type == "fused_attention":
            q, k, v, out = [block.var(n).shape for n in (
                op.inputs["Q"][0], op.inputs["K"][0], op.inputs["V"][0],
                op.outputs["Out"][0])]
            assert q[-1] == k[-1] == 24 and v[-1] == out[-1] == 16
        if op.type == "rotary_embed":
            assert op.attrs["interleaved"] is True
        if op.type == "moe_ffn":
            assert op.attrs["routed_scaling_factor"] == 2.448
            assert op.attrs["norm_topk_eps"] == 1e-20


def test_a_training_step_counts_three_forwards():
    """utils.flops.program_flops counts the core over Q's width and V's:
    every grad op counts twice its forward."""
    from paddle_tpu.utils.flops import program_flops

    forward, _, _, _ = kanana2.kanana2_lm_program(HP, seq_len=SEQ,
                                                  is_test=True)
    assert program_flops(_run(False)[4], batch_hint=BATCH) == (
        3.0 * program_flops(forward, batch_hint=BATCH))
    core = 3 * 2.0 * BATCH * 2 * SEQ * SEQ * (24 + 16)
    with_equal_widths = 3 * 2.0 * 2.0 * BATCH * 2 * SEQ * SEQ * 24
    assert core < with_equal_widths  # 40 a pair, not 48


@pytest.mark.parametrize("key, value, error", [
    ("n_group", 2, NotImplementedError), ("topk_group", 2,
                                          NotImplementedError),
    ("scoring_func", "softmax", NotImplementedError),
    ("topk_method", "greedy", NotImplementedError),
    ("rope_scaling", {"type": "yarn"}, NotImplementedError),
    ("moe_layer_freq", 2, NotImplementedError),
    ("num_key_value_heads", 1, ValueError),
    ("tie_word_embeddings", True, NotImplementedError)])
def test_what_the_builder_would_have_to_guess_it_refuses(key, value, error):
    hp = type("Guess", (HP,), {key: value})
    with pytest.raises(error, match=key.split("_")[0]):
        kanana2.kanana2_lm_program(hp, seq_len=SEQ)


def test_with_a_query_latent_the_program_is_the_reference_with_one():
    """`q_lora_rank` was refused until PR 61: the block now builds
    `latent_attention(q_lora_rank=)` and the reference takes the three
    parameters in W_q's place: loss and every gradient, float32."""
    hp = type("QueryLatent", (HP,), {"q_lora_rank": 24})
    cfg = dict(CFG, q_lora_rank=24)
    main, startup, _, fetches = kanana2.kanana2_lm_program(hp, seq_len=SEQ)
    startup.random_seed = main.random_seed = 5
    batch = gpt2.make_fake_lm_batch(BATCH, SEQ, hp, seed=1)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        every = main.global_block().all_parameters()
        names = [p.name.rsplit("_", 1)[0] for p in every]
        assert "mla_q.w" not in names
        assert names[1:5] == ["attn_norm.w", "mla_q_a.w", "mla_q_a_norm.w",
                              "mla_q_b.w"]
        values = [np.asarray(scope.find_var(p.name)) for p in every]
        with jax.default_matmul_precision("highest"):
            want_loss, want = jax.jit(jax.value_and_grad(
                lambda p: ref.loss(cfg, p, batch)))(
                    [jnp.asarray(v) for v in values])
        trained = [p for p in every if p.trainable]
        out = exe.run(main, feed=batch, fetch_list=[fetches[0]] + [
            main._grad_names[p.name] for p in trained])
    got = float(np.asarray(out[0]).reshape(-1)[0])
    assert abs(got - float(want_loss)) <= 1e-5 * abs(float(want_loss))
    by_name = dict(zip([p.name for p in every], want))
    for p, g in zip(trained, out[1:]):
        g, w = np.asarray(g), np.asarray(by_name[p.name])
        assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max(), p.name
    scopes = {op.attrs.get("op_namescope") for op in main.global_block().ops}
    assert "mla/q_latent" in scopes


# --- the departures ---------------------------------------------------------
def _adapter():
    path = os.path.join(ROOT, "benchmark", "adapters", "kanana2_lm.py")
    spec = importlib.util.spec_from_file_location("kanana2_lm_adapter", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ADAPTER = _adapter()
ADAPTER_CFG = dict(
    {k: CFG[k] for k in ADAPTER._HP_KEYS},
    n_routed_experts=HP.num_local_experts,
    share={"router_experts": HP.n_routed_experts,
           "expert_offset": HP.expert_offset})


# Weights where every departure shows.  At the startup's normal(0, 0.02)
# the scores are ~1e-2, the softmax uniform and the logits ~0: the loss is
# log(vocabulary) whatever the model does, and position, the scale or the
# latent's norm move it by 1e-5.  Attention projections 8 x larger give
# scores of order 1; larger output projections, shared-expert and routed
# down projections and a 20 x selection bias make each branch matter; a
# 15 x head makes the loss read the trunk.
SHOW = {"mla_q.w": 8.0, "mla_kv_a.w": 8.0, "mla_kv_b.w": 8.0,
        "mla_o.w": 4.0, "shared_ffn_gate.w": 8.0, "shared_ffn_up.w": 8.0,
        "shared_ffn_out.w": 4.0, "moe_down.w": 30.0, BIAS: 20.0,
        "softmax_out.w": 15.0}


@functools.lru_cache(maxsize=None)
def _eval_loss_and_references():
    """The dropout-free forward loss of the program on the SHOW weights,
    the adapter's reference on the same weights (exact, with each of its
    deliberate errors, and all in bfloat16), compared as the harness
    compares them (inside the scope the forward-only program ran in, so
    the adapter pairs the program's rows with the reference's), and the
    model's reference: (program loss, {name: reference loss}, the model's
    reference's loss, {name: paired readings})."""
    params = [(n, v * SHOW.get(n.rsplit("_", 1)[0], 1.0))
              for n, v in _run(False)[7]]
    fwd, _, _, fetches = kanana2.kanana2_lm_program(HP, seq_len=SEQ,
                                                    is_test=True)
    batch = gpt2.make_fake_lm_batch(BATCH, SEQ, HP, seed=1)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    refs, found = {}, {}
    with fluid.scope_guard(scope):
        for name, value in params:
            scope.set(name, jnp.asarray(value))
        got = float(np.asarray(exe.run(
            fwd, feed=batch, fetch_list=[fetches[0]])[0]).reshape(-1)[0])
        for name, departure, dtype in (
                [(d, d, "float32") for d in (None,) + ADAPTER.DEPARTURES]
                + [("all_bfloat16", None, "bfloat16")]):
            _, refs[name], found[name] = ADAPTER.compare(
                ADAPTER_CFG, params, batch, departure, dtype)
    with jax.default_matmul_precision("highest"):
        want = float(ref.loss(CFG, [jnp.asarray(v) for _, v in params],
                              batch))
    return got, refs, want, found


def test_the_adapters_reference_is_the_models_reference():
    """Two statements of the same equations, written apart (the adapter's
    attention goes one head at a time): the same loss (float32, 1e-6),
    and the program's."""
    got, refs, want, _ = _eval_loss_and_references()
    assert refs[None] == pytest.approx(want, rel=1e-6)
    assert got == pytest.approx(want, rel=1e-5)


@pytest.mark.parametrize("departure", ADAPTER.DEPARTURES)
def test_each_departure_moves_the_loss_where_the_exact_reference_does_not(
        departure):
    """The program against the reference with ONE deliberate error, on
    the SHOW weights, in float32: each moves the loss by a thousand times
    what the exact reference differs by, and the cell's comparison fails
    it: the loss is outside the adapter's TOLERANCE or the paired costs
    are over their limit."""
    got, refs, _, found = _eval_loss_and_references()
    exact = abs(got - refs[None])
    assert exact <= 5e-6
    moved = abs(got - refs[departure])
    assert moved > 1000 * exact, (departure, got, refs[departure])
    assert (moved > ADAPTER.TOLERANCE
            or found[departure]["cost_rms_over_bf16"]
            > ADAPTER.LIMITS["cost_rms_over_bf16"]), (departure, moved,
                                                      found[departure])


def test_an_all_bfloat16_reference_is_told_from_the_exact_one():
    got, refs, _, found = _eval_loss_and_references()
    assert abs(got - refs["all_bfloat16"]) > 1000 * abs(got - refs[None])
    assert found[None]["cost_rms_over_bf16"] < 0.01
    assert found["all_bfloat16"]["cost_rms_over_bf16"] > 2 * ADAPTER.LIMITS[
        "cost_rms_over_bf16"]


def test_the_forward_only_program_leaves_what_the_comparison_pairs():
    """Every token's cost stays in the scope of an `is_test` program; in
    float32 the rows are the exact reference's to 1e-5."""
    found = _eval_loss_and_references()[3][None]
    assert found["cost_rms"] <= 1e-5
    train = _run(False)[4]
    assert kanana2.EVAL_ROWS not in train.global_block().vars


@pytest.mark.parametrize("departure",
                         ADAPTER.DEPARTURES + ("all_bfloat16",))
def test_each_departure_moves_the_paired_costs(departure):
    """Token by token nothing averages away: on the SHOW weights each
    wrong reference, and the exact one a precision down, differs from the
    program's rows by more than a thousand times what the exact one
    does."""
    found = _eval_loss_and_references()[3]
    assert found[departure]["cost_rms"] > max(
        1e-3, 1000 * found[None]["cost_rms"]), found[departure]


def test_a_paired_reading_over_its_limit_reaches_the_harness_as_nan(
        monkeypatch):
    """loops/train.py takes one float: a reading over its limit makes it
    NaN, which no tolerance admits; without a program's rows in the scope
    the loss comes back as it is."""
    params = [(n, v) for n, v in _run(False)[7]]
    batch = gpt2.make_fake_lm_batch(BATCH, SEQ, HP, seed=1)
    fwd, _, _, fetches = kanana2.kanana2_lm_program(HP, seq_len=SEQ,
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
            ADAPTER_CFG, params, batch, "no_shared_expert"))
        assert np.isnan(ADAPTER.reference_loss(
            ADAPTER_CFG, params, batch, None, "bfloat16"))
        monkeypatch.setattr(ADAPTER, "LIMITS", {"cost_rms": 1e-12})
        assert np.isnan(ADAPTER.reference_loss(ADAPTER_CFG, params, batch))


# --- the share test ---------------------------------------------------------
def _layer_weights():
    rng = np.random.RandomState(7)
    d, e, f = HP.hidden_size, HP.n_routed_experts, HP.moe_intermediate_size
    fs = HP.n_shared_experts * f
    return {"x": rng.randn(BATCH, SEQ, d).astype("float32"),
            "router": (rng.randn(d, e) * 0.3).astype("float32"),
            "bias": (rng.randn(e) * 0.3).astype("float32"),
            "gate_up": (rng.randn(e, d, 2 * f) * 0.2).astype("float32"),
            "down": (rng.randn(e, f, d) * 0.2).astype("float32"),
            "shared": [(rng.randn(d, fs) * 0.2).astype("float32"),
                       (rng.randn(d, fs) * 0.2).astype("float32"),
                       (rng.randn(fs, d) * 0.2).astype("float32")]}


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_layer():
    """Four chips hold experts 0-1, 2-3, 4-5, 6-7 of one layer.  Each
    routes over all eight, computes its own experts' part and the WHOLE
    shared expert; the four routed parts plus the shared expert counted
    ONCE are what the uncut reference gives for the layer (adding the
    four outputs would count the shared expert four times), and every chip
    saw the same routing decisions."""
    w = _layer_weights()
    cfg = dict(CFG, expert_offset=0)
    args = [jnp.asarray(w[k]) for k in ("x", "router", "bias", "gate_up",
                                        "down")]
    with jax.default_matmul_precision("highest"):
        routed, top_e = ref.routed(cfg, *args)
        shared = ref.swiglu_mlp(args[0], *map(jnp.asarray, w["shared"]))
    want_counts = np.bincount(np.asarray(top_e).reshape(-1), minlength=8)
    parts = [share_through_the_executor(decoder.deepseek_v3_experts, HP, w,
                                        offset, 2)
             for offset in (0, 2, 4, 6)]
    for both, part, counts in parts:
        np.testing.assert_array_equal(counts, want_counts)
        assert np.abs(part).max() > 0  # every share has live rows here
        np.testing.assert_allclose(both - part, shared, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(sum(p for _, p, _ in parts) + shared,
                               routed + shared, rtol=1e-5, atol=2e-5)
    # and one share alone is what the reference gives for that share
    with jax.default_matmul_precision("highest"):
        alone, _ = ref.routed(dict(cfg, expert_offset=4), *args[:3],
                              args[3][4:6], args[4][4:6])
    np.testing.assert_allclose(parts[2][1], alone, rtol=1e-5, atol=1e-5)


# --- rotary ----------------------------------------------------------------
def _rotary(x, interleaved):
    main, startup = fluid.Program(), fluid.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        v = layers.data("x", shape=list(x.shape), append_batch_size=False)
        out = layers.rotary_embed(v, base=1e6, interleaved=interleaved)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        return exe.run(main, feed={"x": x}, fetch_list=[out])[0]


@pytest.mark.parametrize("pos", ["none", "T", "BT"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_permutation_product_is_the_strided_slices_bit_for_bit(dtype,
                                                                   pos):
    """`interleaved` undoes the published pairing with a constant
    permutation matmul: the op's result AND its gradient with respect to X
    are the strided slices' to the bit, in float32 (every mantissa bit set:
    not a cast-back of bfloat16) and in bfloat16, whatever `Pos` is."""
    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import nn_ops

    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(2, 3, 16, 8), dtype)
    g = jnp.asarray(rng.randn(2, 3, 16, 8), dtype)
    p = {"none": None, "T": jnp.asarray(rng.randint(0, 99, (16,)), "int32"),
         "BT": jnp.asarray(rng.randint(0, 99, (2, 16)), "int32")}[pos]

    def op(x, **attrs):
        ins = {"X": [x]} if p is None else {"X": [x], "Pos": [p]}
        return nn_ops._rotary_embed(
            LowerCtx(), ins, dict(attrs, base=1e6))["Out"][0]

    def by_slices(x):  # the formulation before PR 43, kept as the reference
        return op(jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1))

    def both(fn):
        out, back = jax.vjp(fn, x)
        return out, back(g)[0]

    got = jax.jit(lambda: both(lambda x: op(x, interleaved=True)))()
    want = jax.jit(lambda: both(by_slices))()
    assert got[0].dtype == want[0].dtype == jnp.dtype(dtype)
    assert np.abs(np.asarray(want[1], "float32")).mean() > 0.1
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_lowered_rope_scope_has_no_strided_slice_and_no_interior_pad(
        monkeypatch):
    """The counter that says the mechanism engages: in a tiny kanana-2's
    train step lowered for the TPU, nothing under `mla.rope` is a `gather`
    or a `scatter` (what JAX 0.9 makes of `x[..., 0::2]` and of its
    transpose: the lowering before PR 43), no `stablehlo.slice` there has a
    stride other than 1 and no `stablehlo.pad` interior padding (what
    `lax.slice` strides would be), forward or backward; the de-interleaves
    are `dot_general`s, one a `rotary_embed` forward and one backward, and
    on a float32 operand (what the AMP pass leaves the scope) they carry
    HIGHEST."""
    import re

    import test_lowered_step_pins as pins

    monkeypatch.setattr(pins.pk, "_interpret", lambda: False)
    jax.clear_caches()  # as the pins lower: the kernels for Mosaic
    text = pins._lowered_step(*pins.PROGRAMS["kanana2"]()).as_text(
        debug_info=True)
    jax.clear_caches()
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    rope = {}
    for line in text.splitlines():
        at = re.search(r"loc\((#loc\d+)\)\s*$", line)
        op = re.search(r"= stablehlo\.(\w+)", line)
        if at and op and "/mla.rope/" in named.get(at.group(1), ""):
            rope.setdefault(op.group(1), []).append(line)
    assert not {"gather", "scatter"} & set(rope)
    assert len(rope["slice"]) >= 16  # the splits and the 32-lane halves
    for line in rope["slice"]:
        spans = re.search(r"\[([^\]]*)\] :", line).group(1).split(", ")
        assert all(len(s.split(":")) == 2 for s in spans), line
    for line in rope["pad"]:
        assert re.search(r"interior = \[0(, 0)*\]", line), line
    dots = rope["dot_general"]
    assert len(dots) == 2 * 2 * 2  # q and k, forward and backward, 2 layers
    for line in dots:
        assert "xf32>, tensor<64x64xf32>" in line
        assert "precision = [HIGHEST, HIGHEST]" in line


def test_rotary_sweep_runs_tiny_and_its_forms_agree():
    """tools/rotary_sweep.py (a chip tool) runs at its rehearsal shapes, and
    every form of the de-interleave it times gives the strided slices'
    result and gradient: bit for bit the op (the permutation product), real
    strided slices and the reshape-transpose, in both dtypes, and the two
    forms that fold the rotation into the product in bfloat16; in float32
    those two round a contracted multiply-add in another order on this
    host, so they are held to one part in a million."""
    spec = importlib.util.spec_from_file_location(
        "rotary_sweep", os.path.join(ROOT, "tools", "rotary_sweep.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    lines = tool.sweep(tool.TINY, (jnp.float32, jnp.bfloat16), iters=1)
    assert len(lines) == len(tool.TINY) * 2 * 7
    exact = {"op", "strided_slices", "reshape_transpose"}
    seen = set()
    for line in lines:
        assert line["ms"] > 0 and line["bytes"] > 0
        seen.add(line["form"])
        if line["form"] in exact or (line["dtype"] == "bfloat16"
                                     and "equal" in line):
            assert line["equal"] and line["equal_grad"], line
        elif "equal" in line:
            assert line["max_diff"] < 1e-6, line
    assert seen == exact | {"slices", "fused_matmul", "two_matmuls",
                            "rotate_half"}


def test_rotary_embeds_two_pairings_agree_after_the_permutation():
    """The published pairing is a de-interleave followed by rotate-half:
    interleaved(x) == rotate_half(x de-interleaved), the result left in
    the de-interleaved order; it is the reference's rotation of the pairs
    (2i, 2i+1) after the same permutation; a q . k score does not see the
    permutation; and the default attribute set is what every program had."""
    rng = np.random.RandomState(3)
    q = rng.randn(2, 3, 16, 8).astype("float32")
    k = rng.randn(2, 1, 16, 8).astype("float32")
    perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    got = _rotary(q, True)
    np.testing.assert_allclose(got, _rotary(q[..., perm], False), rtol=1e-6)
    want = np.asarray(ref.rope_pairs(jnp.asarray(q), 1e6))
    np.testing.assert_allclose(got, want[..., perm], rtol=1e-5, atol=1e-6)
    assert np.abs(got - _rotary(q, False)).max() > 0.1  # not the same turn
    scores = np.einsum("bhqd,bxkd->bhqk", got, _rotary(k, True))
    np.testing.assert_allclose(
        scores, np.einsum("bhqd,bxkd->bhqk", want, np.asarray(
            ref.rope_pairs(jnp.asarray(k), 1e6))), rtol=1e-4, atol=1e-5)
    main = fluid.Program()
    with framework.program_guard(main, fluid.Program()):
        layers.rotary_embed(layers.data("x", shape=[3, 16, 8]))
    (op,) = [o for o in main.global_block().ops if o.type == "rotary_embed"]
    assert "interleaved" not in op.attrs
