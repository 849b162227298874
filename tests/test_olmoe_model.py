"""OLMoE through Executor.run against models/olmoe_reference.py (plain
float32 jax.numpy, experts as a loop over a mask) on seeded weights: the
loss and every parameter's gradient, tight in float32 and at a written
tolerance under the bf16 AMP pass; the program verifies; it trains."""

import functools

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis
from paddle_tpu.models import gpt2, olmoe, olmoe_reference as ref


class HP(olmoe.OLMoEConfig):
    vocab_size = 300
    hidden_size = 64
    intermediate_size = 32
    num_hidden_layers = 2
    num_attention_heads = 2
    num_key_value_heads = 2
    num_experts = 8
    num_experts_per_tok = 2


CFG = {k: getattr(HP, k) for k in dir(HP) if not k.startswith("_")}
SEQ, BATCH = 16, 4
PARAMS = ["emb.w_0", "attn_norm.w_0", "mha_q.w_0", "mha_k.w_0", "mha_v.w_0",
          "mha_q_norm.w_0", "mha_k_norm.w_0", "mha_o.w_0", "ffn_norm.w_0",
          "moe_router.w_0", "moe_gate_up.w_0", "moe_down.w_0",
          "attn_norm.w_1", "moe_router.w_1", "moe_gate_up.w_1",
          "moe_down.w_1", "final_norm.w_0", "softmax_out.w_0"]


@functools.lru_cache(maxsize=None)
def _run(use_bf16):
    """(program loss, {param: grad}, reference loss, {param: grad}, the
    program, losses of three training steps, tokens-per-expert of layer
    0) on seeded weights."""
    main, startup, _, fetches = olmoe.olmoe_lm_program(
        HP, seq_len=SEQ, lr=1e-3, use_bf16=use_bf16)
    startup.random_seed = main.random_seed = 5
    batch = gpt2.make_fake_lm_batch(BATCH, SEQ, HP, seed=1)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        params = [(p.name, np.asarray(scope.find_var(p.name)))
                  for p in main.global_block().all_parameters()]
        want_loss, want_grads = ref.loss_and_grads(
            CFG, [v for _, v in params], batch)
        out = exe.run(main, feed=batch, fetch_list=[fetches[0]] + [
            main._grad_names[n] for n, _ in params])
        steps = [float(np.asarray(out[0]).reshape(-1)[0])] + [
            float(np.asarray(exe.run(
                main, feed=batch, fetch_list=[fetches[0]])[0]).reshape(-1)[0])
            for _ in range(2)]
        counts = np.asarray(scope.find_var("moe_tokens_per_expert_0"))
    names = [n for n, _ in params]
    return (steps[0], dict(zip(names, out[1:])), float(want_loss),
            dict(zip(names, want_grads)), main, steps, counts)


def test_every_parameter_is_created_in_the_references_order():
    names = [p.name for p in _run(False)[4].global_block().all_parameters()]
    assert len(names) == 3 + 11 * HP.num_hidden_layers
    assert [n for n in names if n in PARAMS] == PARAMS


def test_float32_loss_matches_the_reference():
    got, _, want, _, _, _, _ = _run(False)
    assert abs(got - want) <= 1e-5 * abs(want), (got, want)


@pytest.mark.parametrize("name", PARAMS)
def test_float32_gradient_matches_the_reference(name):
    """The same arithmetic in another order: 1e-4 of the gradient's
    largest element (measured: 4e-7 or less)."""
    _, got, _, want, _, _, _ = _run(False)
    g, w = np.asarray(got[name]), np.asarray(want[name])
    assert g.shape == w.shape
    assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max(), name


def test_bf16_amp_loss_matches_the_reference_within_its_tolerance():
    """bf16 matmuls against float32 "highest": 2.1e-5 measured on a loss
    of 5.74 at these widths; 2e-3 is what benchmark/adapters/gpt2_lm.py
    allows the same recipe.  A router in bf16 or a dropped aux term is
    caught by the float32 cases above, which are exact."""
    got, _, want, _, _, _, _ = _run(True)
    assert abs(got - want) <= 2e-3, (got, want)


@pytest.mark.parametrize("name", ["emb.w_0", "mha_q.w_0", "mha_q_norm.w_0",
                                  "moe_router.w_0", "moe_gate_up.w_1",
                                  "moe_down.w_1", "softmax_out.w_0"])
def test_bf16_amp_gradient_is_close_to_the_reference(name):
    """bf16 rounding of every activation, and at this size one or two of
    the 128 routing decisions of a layer flipped by it: 10% of the
    gradient's largest element (measured: up to 6%, on the router)."""
    _, got, _, want, _, _, _ = _run(True)
    g, w = np.asarray(got[name]), np.asarray(want[name])
    assert np.abs(g - w).max() <= 0.1 * np.abs(w).max(), name


@pytest.mark.parametrize("use_bf16", [False, True], ids=["f32", "bf16_amp"])
def test_program_verifies_and_trains(use_bf16):
    _, _, _, _, main, steps, counts = _run(use_bf16)
    diags = analysis.verify_program(main)
    assert not [d for d in diags if d.is_error], diags
    assert steps[2] < steps[1] < steps[0], steps
    assert counts.sum() == BATCH * SEQ * HP.num_experts_per_tok


def test_an_eval_program_keeps_its_own_router_statistic():
    main, _, _, _ = olmoe.olmoe_lm_program(HP, seq_len=SEQ, is_test=True)
    stats = [n for n in main.global_block().vars
             if n.startswith("moe_tokens_per_expert")]
    assert stats == ["moe_tokens_per_expert_eval_0",
                     "moe_tokens_per_expert_eval_1"]


def test_gpt2_builder_still_builds_the_same_program():
    """The plumbing moved into lm_train_program: the op sequence of the
    GPT-2 train program is what it was."""
    class Tiny(gpt2.GPT2Config):
        vocab_size, n_ctx, d_model, n_layer, n_head = 100, 16, 32, 1, 2

    main, _, feeds, fetches = gpt2.gpt2_lm_program(Tiny, seq_len=8)
    types = [op.type for op in main.global_block().ops]
    assert feeds == ["ids", "labels", "loss_weight"] and len(fetches) == 2
    assert types.count("fused_linear_xent") == 1
    assert types.count("fused_attention") == 1
    assert "moe_ffn" not in types and "rms_norm" not in types
    assert "expert_bias_update" not in types  # nothing selects with a bias
    assert types.count("adam") == len(main.global_block().all_parameters())
