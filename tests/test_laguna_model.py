"""Laguna-XS.2 through Executor.run against models/laguna_reference.py
(plain float32 jax.numpy: attention as an explicit softmax under a mask
built from positions, both rotaries and YaRN's frequencies written out,
experts as a loop over a mask) on seeded weights, at the small widths of
the benchmark configuration's `rehearse` (8 and 6 query heads over 2 KV
heads of 32 that are not 64 / heads, a window of 8 over 32 positions, the
dense full layer, three sliding layers and a full one, 4 of the router's
16 experts held): the loss, every token's cost and every parameter's
gradient, tight in float32 and at a written tolerance under the bf16 AMP
pass; YaRN's numbers at the published keys; every deliberate error the
benchmark's comparison has to catch, on weights where it shows; the shares
of an expert layer and the shared expert counted once add up to the uncut
layer; the models that share the attention builder build the Programs
they built; the flash kernels under a window of half a block, one and two;
the program verifies; it trains."""

import functools
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, layers
from paddle_tpu.core.registry import LowerCtx
from paddle_tpu.models import gpt2, laguna, laguna_reference as ref
from paddle_tpu.models import transformer as tfm
from paddle_tpu.ops import nn_ops

from expert_share import share_through_the_executor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "laguna_xs2_33b_a3b"


def _adapter():
    path = os.path.join(ROOT, "benchmark", "adapters", "laguna_lm.py")
    spec = importlib.util.spec_from_file_location("laguna_lm_adapter", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(rehearse):
    """benchmark/configs/laguna_xs2_33b_a3b.json, with its `rehearse`
    sizes laid over the published ones as benchmark/run.py --rehearse
    reads it."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           NAME + ".json")) as f:
        data = json.load(f)
    cfg = {k: v for k, v in data.items() if k != "rehearse"}
    for k, v in (data["rehearse"] if rehearse else {}).items():
        both = isinstance(v, dict) and isinstance(cfg.get(k), dict)
        cfg[k] = dict(cfg[k], **v) if both else v
    return cfg


ADAPTER = _adapter()
ADAPTER_CFG = _config(True)
CFG = ADAPTER._arch(ADAPTER_CFG)
HP = type("HP", (laguna.LagunaConfig,), dict(CFG))
SEQ, BATCH = 32, 4
ATTN = ["input_norm.w", "mha_q.w", "mha_k.w", "mha_v.w", "mha_gate.w",
        "mha_o.w", "pre_mlp_norm.w"]
DENSE = ["ffn_gate.w", "ffn_up.w", "ffn_out.w"]
MOE = ["moe_router.w", "moe_gate_up.w", "moe_down.w", "shared_ffn_gate.w",
       "shared_ffn_up.w", "shared_ffn_out.w"]
ORDER = (["emb.w"] + ATTN + DENSE + (ATTN + MOE) * 4
         + ["final_norm.w", "softmax_out.w"])
FULL = HP.rope_parameters["full_attention"]


@functools.lru_cache(maxsize=None)
def _run(use_bf16):
    """(program loss, {param: grad}, reference loss, {param: grad}, the
    program, losses of three training steps, tokens-per-expert of the
    first expert layer, the startup weights) on seeded weights."""
    main, startup, _, fetches = laguna.laguna_lm_program(
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
        out = exe.run(main, feed=batch, fetch_list=[fetches[0]] + [
            main._grad_names[p.name] for p in every])
        steps = [float(np.asarray(out[0]).reshape(-1)[0])] + [
            float(np.asarray(exe.run(
                main, feed=batch, fetch_list=[fetches[0]])[0]).reshape(-1)[0])
            for _ in range(2)]
        counts = np.asarray(scope.find_var("moe_tokens_per_expert_0"))
    names = [p.name for p in every]
    return (steps[0], dict(zip(names, out[1:])), float(want_loss),
            dict(zip(names, want_grads)), main, steps, counts,
            list(zip(names, values)))


def test_the_published_config_is_the_class_default():
    """The class, the configuration file before its cut and the catalog
    row the issue quotes say the same."""
    hp = laguna.LagunaConfig
    assert (hp.num_hidden_layers, hp.hidden_size, hp.num_key_value_heads,
            hp.head_dim, hp.vocab_size, hp.intermediate_size) == (
                40, 2048, 8, 128, 100352, 8192)
    assert hp.layer_types == (["full_attention"]
                              + ["sliding_attention"] * 3) * 10
    assert hp.num_attention_heads_per_layer == [48, 64, 64, 64] * 10
    assert hp.mlp_layer_types == ["dense"] + ["sparse"] * 39
    assert (hp.sliding_window, hp.rms_norm_eps, hp.gating) == (512, 1e-6,
                                                               True)
    assert (hp.num_experts, hp.num_experts_per_tok, hp.moe_intermediate_size,
            hp.shared_expert_intermediate_size,
            hp.moe_routed_scaling_factor) == (256, 8, 512, 512, 2.5)
    assert not hp.tie_word_embeddings and not hp.attention_bias
    published = _config(False)
    assert published["rope_parameters"]["full_attention"] == \
        hp.rope_parameters["full_attention"]
    assert published["rope_parameters"]["sliding_attention"] == \
        hp.rope_parameters["sliding_attention"]
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert published[key] == getattr(hp, key)[:5]


def test_the_rehearsal_keeps_what_makes_the_model():
    """Two head counts that both differ from hidden / head_dim, one no
    power of two, grouped 4 and 3 to a KV head; a window shorter than the
    sequence; both kinds of layer with the published rotaries; a share."""
    assert HP.num_attention_heads_per_layer == [6, 8, 8, 8, 6]
    assert HP.num_key_value_heads == 2 and HP.head_dim * 8 != HP.hidden_size
    assert 0 < HP.sliding_window < SEQ
    assert HP.layer_types == (["full_attention"] + ["sliding_attention"] * 3
                              + ["full_attention"])
    assert HP.rope_parameters == laguna.LagunaConfig.rope_parameters
    assert HP.num_local_experts < HP.num_experts and HP.expert_offset
    # the ramp lies inside the rotary's 8 pairs at the small head too
    assert ref.yarn_range(FULL, 16) == (1, 4)


def test_every_parameter_is_created_in_the_references_order():
    block = _run(False)[4].global_block()
    names = [p.name for p in block.all_parameters()]
    assert [n.rsplit("_", 1)[0] for n in names] == ORDER
    shapes = {n: tuple(block.var(n).shape) for n in names}
    assert shapes["mha_q.w_0"] == (64, 6 * 32)     # a full layer's heads
    assert shapes["mha_q.w_1"] == (64, 8 * 32)     # a sliding layer's
    assert shapes["mha_k.w_0"] == shapes["mha_v.w_1"] == (64, 2 * 32)
    assert shapes["mha_gate.w_0"] == (64, 6)       # one gate a head
    assert shapes["mha_gate.w_1"] == (64, 8)
    assert shapes["mha_o.w_0"] == (6 * 32, 64)
    assert shapes["mha_o.w_1"] == (8 * 32, 64)
    assert shapes["moe_router.w_0"] == (64, 16)  # the router's full width
    assert shapes["moe_gate_up.w_0"] == (4, 64, 64)  # four experts held
    assert shapes["shared_ffn_gate.w_0"] == (64, 32)
    assert shapes["softmax_out.w_0"] == (64, 256)  # the head is its own
    assert not [n for n in names if "bias" in n or "qk" in n or "_norm.w" in n
                and n.startswith("mha")]


def test_no_router_selects_with_a_bias_and_no_step_balances_one():
    """No key names a selection bias: the `moe_ffn` ops take none and the
    training program ends with the optimizer."""
    block = _run(False)[4].global_block()
    moe = [op for op in block.ops if op.type == "moe_ffn"]
    assert len(moe) == 4 and not any(op.inputs.get("ExpertBias")
                                     for op in moe)
    assert "expert_bias_update" not in [op.type for op in block.ops]
    for op in moe:
        assert op.attrs["router"] == "sigmoid" and op.attrs["norm_topk_prob"]
        assert op.attrs["routed_scaling_factor"] == 2.5
        assert op.attrs["norm_topk_eps"] == 1e-20
        assert (op.attrs["top_k"], op.attrs["expert_offset"]) == (4, 4)


def test_float32_loss_matches_the_reference():
    got, _, want, _, _, _, _, _ = _run(False)
    assert abs(got - want) <= 1e-5 * abs(want), (got, want)


@pytest.mark.parametrize("base", list(dict.fromkeys(ORDER)))
def test_float32_gradient_matches_the_reference(base):
    """Every parameter of that kind, in every layer: the same arithmetic
    in another order, 1e-4 of the gradient's largest element."""
    _, got, _, want, _, _, _, _ = _run(False)
    names = [n for n in got if n.rsplit("_", 1)[0] == base]
    assert names
    for name in names:
        g, w = np.asarray(got[name]), np.asarray(want[name])
        assert g.shape == w.shape
        assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max(), name


def test_bf16_amp_loss_matches_the_reference_within_its_tolerance():
    """bf16 matmuls against float32 "highest": 1.9e-4 measured on a loss
    of 5.56 at these widths."""
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
    assert types.count("fused_attention") == 5 and types.count("moe_ffn") == 4
    # the dense layer's MLP and the four shared experts
    assert types.count("fused_swiglu") == 5
    assert types.count("fused_linear_xent") == 1


def _by_scope(block):
    found = {}
    for op in block.ops:
        found.setdefault(op.attrs.get("op_namescope"), []).append(op)
    return found


def test_each_kind_of_layer_builds_its_own_attention():
    """A sliding_attention layer: 8 heads, rotary over all 32 lanes at
    theta 10,000 with no scaled frequencies, a window on the core, under
    attn_window; a full_attention layer: 6 heads, rotary on the first 16
    lanes (split, rotary_embed, concat) at theta 500,000 with every YaRN
    key and the factor on the op, no window, under attn_full; both: the
    KV heads repeated to the layer's own head count, `rope` around the
    turn, the gate's projection, sigmoid and product under attn_gate, the
    core under core; under the AMP pass the gate's sigmoid and product are
    float32."""
    block = _run(False)[4].global_block()
    by_scope = _by_scope(block)
    assert {"attn_window", "attn_window/core", "attn_window/attn_gate",
            "attn_window/rope", "attn_full", "attn_full/core",
            "attn_full/attn_gate", "attn_full/rope",
            "shared_expert"} <= set(by_scope)
    sliding = [op for op in by_scope["attn_window/core"]
               if op.type == "fused_attention"]
    assert [op.attrs["window"] for op in sliding] == [8] * 3
    full = [op for op in by_scope["attn_full/core"]
            if op.type == "fused_attention"]
    assert [op.attrs["window"] for op in full] == [0] * 2
    for ops, heads in ((sliding, 8), (full, 6)):
        for op in ops:
            assert op.attrs["causal"] and op.attrs["scale"] == 32 ** -0.5
            for slot in ("Q", "K", "V"):
                assert tuple(block.var(op.inputs[slot][0]).shape)[1:] == (
                    heads, SEQ, 32)
    plain = [op for op in by_scope["attn_window/rope"]
             if op.type == "rotary_embed"]
    assert len(plain) == 2 * 3 and all(
        op.attrs["base"] == 10000.0 and "yarn_factor" not in op.attrs
        and "attention_factor" not in op.attrs for op in plain)
    assert {op.type for op in by_scope["attn_window/rope"]} == {
        "rotary_embed", "rotary_embed_grad"}
    scaled = [op for op in by_scope["attn_full/rope"]
              if op.type == "rotary_embed"]
    assert len(scaled) == 2 * 2
    for op in scaled:
        assert tuple(block.var(op.inputs["X"][0]).shape)[-1] == 16
        assert (op.attrs["base"], op.attrs["yarn_factor"],
                op.attrs["yarn_original_max_position"],
                op.attrs["yarn_beta_fast"], op.attrs["yarn_beta_slow"],
                op.attrs["attention_factor"]) == (
                    500000.0, 64.0, 4096.0, 64.0, 1.0, 1.4158883083359672)
    assert {"split", "concat"} <= {op.type for op in by_scope["attn_full/rope"]}
    assert len([op for op in block.ops if op.type == "rotary_embed"]) == 10
    for kind in ("attn_window", "attn_full"):
        gate = {"mul", "sigmoid", "unsqueeze2", "elementwise_mul"}
        assert {op.type for op in by_scope[kind + "/attn_gate"]} == gate | {
            t + "_grad" for t in gate}
    assert "fused_swiglu" in {op.type for op in by_scope["shared_expert"]}
    assert not [op for op in block.ops if op.type == "scale"
                and op.attrs.get("op_role") == "forward"]
    amp = _run(True)[4].global_block()
    for kind in ("attn_window", "attn_full"):
        for op in _by_scope(amp)[kind + "/attn_gate"]:
            if op.type in ("sigmoid", "elementwise_mul"):
                outs = [v for vs in op.outputs.values() for v in vs]
                assert all(str(amp.var(v).dtype) == "float32"
                           for v in outs), op.type


def test_a_window_layers_core_is_counted_over_the_band():
    """utils.flops.program_flops: every grad op counts twice its forward,
    and a core with a window counts Tq x window pairs at ITS heads."""
    from paddle_tpu.utils.flops import program_flops

    forward, _, _, _ = laguna.laguna_lm_program(HP, seq_len=SEQ, is_test=True)
    got = program_flops(forward, batch_hint=BATCH)
    assert program_flops(_run(False)[4], batch_hint=BATCH) == 3.0 * got
    full = type("Full", (HP,), {"sliding_window": SEQ})
    everywhere, _, _, _ = laguna.laguna_lm_program(full, seq_len=SEQ,
                                                   is_test=True)
    pairs = 2.0 * BATCH * 8 * SEQ * (32 + 32)  # QK^T and PV, a key column
    assert program_flops(everywhere, batch_hint=BATCH) - got == (
        3 * pairs * (SEQ - 8))


@pytest.mark.parametrize("key, value, error", [
    ("tie_word_embeddings", True, NotImplementedError),
    ("attention_bias", True, NotImplementedError),
    ("moe_apply_router_weight_on_input", True, NotImplementedError),
    ("gating", "per-lane", NotImplementedError),
    ("layer_types", ["sliding_attention"] * 4 + ["conv"], ValueError),
    ("mlp_layer_types", ["dense"] * 4 + ["both"], ValueError),
    ("num_hidden_layers", 4, ValueError),
    ("layer_types", ["full_attention"] * 4, ValueError),
    ("mlp_layer_types", ["sparse"] * 6, ValueError),
    ("num_attention_heads_per_layer", [6, 8, 8, 8], ValueError),
    ("num_attention_heads_per_layer", [6, 8, 8, 8, 5], ValueError),
    ("rope_parameters", dict(HP.rope_parameters, full_attention=dict(
        FULL, rope_type="llama3")), NotImplementedError),
    ("rope_parameters", {"sliding_attention": HP.rope_parameters[
        "sliding_attention"]}, NotImplementedError),
    ("rope_parameters", dict(HP.rope_parameters, full_attention=dict(
        FULL, mscale=1.0)), NotImplementedError)])
def test_what_the_builder_would_have_to_guess_it_refuses(key, value, error):
    hp = type("Guess", (HP,), {key: value})
    with pytest.raises(error):
        laguna.laguna_lm_program(hp, seq_len=SEQ)


def test_without_gating_no_gate_is_built():
    """Where no key names a mechanism none is built."""
    hp = type("Plain", (HP,), {"gating": False})
    main, _, _, _ = laguna.laguna_lm_program(hp, seq_len=SEQ, is_test=True)
    block = main.global_block()
    assert not [p for p in block.all_parameters() if "gate.w" in p.name
                and p.name.startswith("mha")]
    assert not [s for s in _by_scope(block) if s and s.endswith("attn_gate")]


# --- YaRN -------------------------------------------------------------------
def test_yarn_numbers_at_the_published_keys():
    """dim 64 (half of the 128-wide head), theta 500,000, factor 64 over
    4,096 original positions, beta_fast 64, beta_slow 1.  By hand: the pair
    that turns b times over 4,096 positions is 64 ln(4096 / (2 pi b)) /
    (2 ln 500000): b = 64 -> 64 x 2.32106 / 26.2447 = 5.66, floor 5; b = 1
    -> 64 x 6.47999 / 26.2447 = 15.80, ceiling 16.  r_i = clip((i - 5) / 11,
    0, 1); inv_freq_i = (1 - r_i) / p_i + r_i / (64 p_i), p_i = 500000^(i /
    32): i = 0: 1; i = 10: p = 60.4196, r = 5/11: (6/11 + 5/704) / 60.4196 =
    9.15058e-3; i = 31: p = 331,802.7, r = 1: 1 / (64 p) = 4.70915e-8.
    attention_factor 1.4158883 = 0.1 ln 64 + 1.  The lowering, the model's
    reference and the adapter's say the same."""
    assert nn_ops.yarn_correction_range(64, 500000.0, 4096.0, 64.0, 1.0) == (
        5, 16)
    assert ref.yarn_range(FULL, 64) == (5, 16)
    assert ADAPTER.yarn_range(FULL, 64) == (5, 16)
    attrs = {"yarn_factor": 64.0, "yarn_original_max_position": 4096.0,
             "yarn_beta_fast": 64.0, "yarn_beta_slow": 1.0}
    got = np.asarray(nn_ops._rotary_inv_freq(32, 500000.0, attrs))
    assert got.dtype == np.float32 and got.shape == (32,)
    for i, want in ((0, 1.0), (10, 9.15058e-3), (31, 4.70915e-8)):
        assert got[i] == pytest.approx(want, rel=2e-5), i
    np.testing.assert_allclose(got, np.asarray(ref.inv_freq(FULL, 64)),
                               rtol=1e-6)
    plain = np.asarray(nn_ops._rotary_inv_freq(32, 500000.0, {}))
    np.testing.assert_allclose(got[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(got[16:], plain[16:] / 64, rtol=1e-6)
    assert FULL["attention_factor"] == pytest.approx(
        0.1 * np.log(64.0) + 1.0, rel=1e-9)


def _rotary(x, **kwargs):
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        v = layers.data("x", shape=list(x.shape), append_batch_size=False)
        out = layers.rotary_embed(v, **kwargs)
    (op,) = [o for o in main.global_block().ops if o.type == "rotary_embed"]
    got = fluid.Executor(fluid.CPUPlace()).run(main, feed={"x": x},
                                               fetch_list=[out])[0]
    return np.asarray(got), op


def test_rotary_embed_with_scaling_is_the_references_turn():
    """The layer's op against the reference's written-out rotary at the
    published keys over 64 lanes; the default attention_factor is 0.1
    ln(factor) + 1; without `scaling` the op carries what it carried."""
    x = np.random.RandomState(3).randn(2, 3, 24, 64).astype("float32")
    scaling = {k: v for k, v in FULL.items()
               if k not in ("rope_theta", "partial_rotary_factor")}
    got, op = _rotary(x, base=500000.0, scaling=scaling)
    whole = dict(FULL, partial_rotary_factor=1)
    np.testing.assert_allclose(got, np.asarray(ref.rope(
        jnp.asarray(x), whole, 64)), rtol=1e-5, atol=1e-5)
    assert np.abs(got - _rotary(x, base=500000.0)[0]).max() > 0.1
    _, bare = _rotary(x, base=500000.0, scaling={
        k: v for k, v in scaling.items() if k != "attention_factor"})
    assert bare.attrs["attention_factor"] == pytest.approx(
        FULL["attention_factor"], rel=1e-9)
    _, plain = _rotary(x)
    assert set(plain.attrs) - {"op_role", "op_namescope"} == {"base"}


@pytest.mark.parametrize("scaling, pos", [
    ({"rope_type": "llama3", "factor": 8.0}, False),
    ({"rope_type": "yarn", "factor": 64.0, "mscale": 1.0,
      "original_max_position_embeddings": 4096}, False),
    ({"rope_type": "yarn", "factor": 64.0,
      "original_max_position_embeddings": 4096}, True)])
def test_rotary_embed_refuses_what_it_has_no_frequencies_for(scaling, pos):
    """Another rope_type, a key YaRN here does not read, and positions fed
    by a cached decode path."""
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = layers.data("x", shape=[2, 3, 8, 16], append_batch_size=False)
        p = layers.data("p", shape=[8], dtype="int64",
                        append_batch_size=False) if pos else None
        with pytest.raises(NotImplementedError):
            layers.rotary_embed(x, pos=p, scaling=scaling)


def test_the_lowering_refuses_positions_with_scaled_frequencies():
    x = jnp.zeros((1, 2, 4, 8))
    with pytest.raises(NotImplementedError, match="cached decode"):
        nn_ops._rotary_embed(LowerCtx(), {"X": [x], "Pos": [jnp.arange(4)]},
                             {"base": 1e4, "yarn_factor": 4.0})


def test_the_cache_paths_refuse_rotary_scaling_in_words():
    scaling = {k: v for k, v in FULL.items()
               if k not in ("rope_theta", "partial_rotary_factor")}
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = layers.data("x", shape=[2, 1, 64], append_batch_size=False)
        with pytest.raises(ValueError, match="no scaled frequencies yet"):
            tfm.multi_head_attention(
                x, x, x, None, 64, 2, cache={"k": None, "v": None},
                rotary=True, rotary_scaling=scaling)
        with pytest.raises(ValueError, match="out_gate"):
            tfm.multi_head_attention(x, x, x, None, 64, 2, out_gate="lane")


# --- the departures ---------------------------------------------------------
# Weights where every departure shows.  At the startup's normal(0, 0.02)
# the scores are ~0 (every softmax uniform: neither rotary, nor its
# frequencies, nor the grouping of the heads moves anything), the gate's
# argument is ~0.1 and sigmoid a constant 0.5, the router's scores are all
# ~0.5, the branches are small beside the residual, and the logits are ~0:
# the loss is log(vocabulary) whatever the trunk computes.  Larger q / k
# (scores of order one), a 30 x gate and router, larger value / output /
# routed / shared projections, an 8 x embedding and a 5 x head make each
# matter without making the all-bfloat16 unit so large that a small branch
# hides under it; the norms' gains are drawn from 0.2 .. 3.
SHOW = {"emb.w": 8.0, "mha_q.w": 8.0, "mha_k.w": 8.0, "mha_v.w": 6.0,
        "mha_o.w": 4.0, "mha_gate.w": 30.0, "moe_router.w": 30.0,
        "moe_gate_up.w": 3.0, "moe_down.w": 10.0, "shared_ffn_gate.w": 6.0,
        "shared_ffn_up.w": 6.0, "shared_ffn_out.w": 6.0, "ffn_out.w": 3.0,
        "softmax_out.w": 5.0}


def _show_weights():
    rng = np.random.RandomState(11)
    out = []
    for name, value in _run(False)[7]:
        base = name.rsplit("_", 1)[0]
        if base.endswith("norm.w"):
            value = rng.uniform(0.2, 3.0, value.shape).astype("float32")
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
    fwd, _, _, fetches = laguna.laguna_lm_program(HP, seq_len=SEQ,
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
                dict(ADAPTER_CFG, reference_limits=ADAPTER.LIMITS), params,
                batch, departure, dtype)
    weights = [jnp.asarray(v) for _, v in params]
    with jax.default_matmul_precision("highest"):
        want = (float(ref.loss(CFG, weights, batch)),
                np.asarray(ref.token_costs(CFG, weights, batch)))
    return got, refs, want, found, rows


def _over_a_limit(found):
    return any(found[k] > ADAPTER.LIMITS[k] for k in ADAPTER.LIMITS)


def test_the_adapters_reference_is_the_models_reference():
    """Two statements of the same equations, written apart (the adapter's
    attention goes one head at a time): the same loss (float32, 1e-6),
    and the program's; every token's cost as well."""
    got, refs, (want, want_rows), _, rows = _eval_loss_and_references()
    assert refs[None] == pytest.approx(want, rel=1e-6)
    assert got == pytest.approx(want, rel=1e-5)
    np.testing.assert_allclose(rows, want_rows, rtol=1e-4, atol=1e-5)


def test_the_departures_are_the_issues():
    assert set(ADAPTER.DEPARTURES) == {
        "heads_swapped", "window_minus_one", "window_plus_one",
        "rope_whole_on_full", "plain_freq_on_full", "no_attention_factor",
        "thetas_swapped", "gate_per_lane", "gate_on_shared", "no_gate",
        "softmax_scores", "no_renormalisation", "no_routed_scale",
        "no_shared_expert"}


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
    assert moved > 1000 * exact, (departure, got, refs[departure])
    assert moved > ADAPTER.TOLERANCE or _over_a_limit(found[departure]), (
        departure, moved, found[departure])


def test_an_all_bfloat16_reference_is_told_from_the_exact_one():
    """A float32 program is the exact reference's to 1e-5 of the unit and
    reads the all-bfloat16 one at its own unit, 1, which is over the
    limit."""
    got, refs, _, found, _ = _eval_loss_and_references()
    assert abs(got - refs["all_bfloat16"]) > 1000 * abs(got - refs[None])
    for reading in ("cost_rms_over_bf16", "cost_median_over_bf16"):
        assert found[None][reading] < 0.01
        assert found["all_bfloat16"][reading] == pytest.approx(1.0, abs=1e-3)
    # the median's limit is the one there is: the rms is a reading
    assert set(ADAPTER.LIMITS) == {"cost_median_over_bf16"}
    assert ADAPTER.LIMITS["cost_median_over_bf16"] < 0.99
    assert _over_a_limit(found["all_bfloat16"])


def test_the_forward_only_program_leaves_what_the_comparison_pairs():
    """Every token's cost stays in the scope of an `is_test` program; in
    float32 the rows are the exact reference's to 1e-5."""
    found = _eval_loss_and_references()[3][None]
    assert found["cost_rms"] <= 1e-5
    train = _run(False)[4]
    assert laguna.EVAL_ROWS not in train.global_block().vars


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
    assert _over_a_limit(found[departure]), found[departure]


@pytest.mark.parametrize("wrong", [
    {"num_attention_heads_per_layer": [8, 6, 6, 6, 8]},
    {"mlp_layer_types": ["sparse"] * 5}],
    ids=["head_counts_swapped_between_the_kinds", "layer_0_sparse"])
def test_a_departure_in_the_lists_does_not_fit_the_programs_weights(wrong):
    """The two departures that change the parameter list cannot be run on
    the program's weights at all: a reference built from the wrong lists
    refuses them by shape, so the comparison raises and cannot pass; and
    a program built from the wrong lists has another parameter list than
    the right one."""
    params = _run(False)[7]
    batch = gpt2.make_fake_lm_batch(BATCH, SEQ, HP, seed=1)
    with pytest.raises(ValueError, match="expected a parameter of shape"):
        ADAPTER.reference(dict(ADAPTER_CFG, **wrong), params, batch)
    main, _, _, _ = laguna.laguna_lm_program(
        type("Wrong", (HP,), wrong), seq_len=SEQ, is_test=True)
    block = main.global_block()
    assert [(p.name, tuple(p.shape)) for p in block.all_parameters()] != [
        (n, v.shape) for n, v in params]


def test_a_paired_reading_over_its_limit_reaches_the_harness_as_nan(
        monkeypatch):
    """loops/train.py takes one float: a reading over its limit makes it
    NaN, which no tolerance admits; without a program's rows in the scope
    the loss comes back as it is; a rehearsal's data may carry limits of
    its own, the measured configuration has none."""
    params = _show_weights()
    batch = gpt2.make_fake_lm_batch(BATCH, SEQ, HP, seed=1)
    fwd, _, _, fetches = laguna.laguna_lm_program(HP, seq_len=SEQ,
                                                  is_test=True)
    cfg = {k: v for k, v in ADAPTER_CFG.items() if k != "reference_limits"}
    assert "reference_limits" not in _config(False)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        assert ADAPTER.program_rows() is None
        plain = ADAPTER.reference_loss(cfg, params, batch)
        for name, value in params:
            scope.set(name, jnp.asarray(value))
        exe.run(fwd, feed=batch, fetch_list=[fetches[0]])
        assert ADAPTER.reference_loss(cfg, params, batch) == plain
        assert np.isnan(ADAPTER.reference_loss(cfg, params, batch,
                                               "no_gate"))
        assert np.isnan(ADAPTER.reference_loss(cfg, params, batch, None,
                                               "bfloat16"))
        loose = dict(cfg, reference_limits={"cost_median_over_bf16": 2.0})
        assert ADAPTER.reference_loss(loose, params, batch, None,
                                      "bfloat16") != plain
        monkeypatch.setattr(ADAPTER, "LIMITS", {"cost_rms": 1e-12})
        assert np.isnan(ADAPTER.reference_loss(cfg, params, batch))


# --- the share test ---------------------------------------------------------
SHARES = 8


class Wide(HP):
    """One layer as eight chips share it: a router over 16 experts, top-4,
    two experts a chip."""
    num_experts, num_experts_per_tok = 2 * SHARES, 4


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
                       (rng.randn(fs, d) * 0.2).astype("float32")]}


def test_the_eight_shares_and_the_shared_expert_once_are_the_layer():
    """Eight chips hold two experts each of one layer.  Each routes over
    all sixteen, computes its own experts' part and the WHOLE shared
    expert; the eight routed parts plus the shared expert counted ONCE are
    what the uncut reference gives for the layer (adding the eight outputs
    would count the shared expert eight times), and every chip saw the
    same routing decisions."""
    w = _layer_weights()
    cfg = dict({k: getattr(Wide, k) for k in dir(Wide)
                if not k.startswith("_")}, expert_offset=0)
    args = [jnp.asarray(w[k]) for k in ("x", "router", "gate_up", "down")]
    with jax.default_matmul_precision("highest"):
        routed, top_e = ref.routed(cfg, *args)
        shared = ref.swiglu_mlp(args[0], *map(jnp.asarray, w["shared"]))
    want_counts = np.bincount(np.asarray(top_e).reshape(-1),
                              minlength=Wide.num_experts)
    parts = [share_through_the_executor(laguna._experts, Wide, w, 2 * rank, 2)
             for rank in range(SHARES)]
    for both, part, counts in parts:
        np.testing.assert_array_equal(counts, want_counts)
        np.testing.assert_allclose(both - part, shared, rtol=1e-4, atol=1e-4)
    assert all(np.abs(part).max() > 0 for _, part, _ in parts)
    np.testing.assert_allclose(sum(p for _, p, _ in parts) + shared,
                               routed + shared, rtol=1e-5, atol=5e-5)
    # and one share alone is what the reference gives for that share
    with jax.default_matmul_precision("highest"):
        alone, _ = ref.routed(dict(cfg, expert_offset=10), *args[:2],
                              args[2][10:12], args[3][10:12])
    np.testing.assert_allclose(parts[5][1], alone, rtol=1e-5, atol=1e-5)


# --- the models that share the attention builder ----------------------------
def _digest(main, unscoped=None):
    """(ops, parameters, a digest of every op's type, inputs, outputs and
    attributes in order and every parameter's name and shape); `unscoped`
    takes that name scope's part off every op's `op_namescope` first (an
    op left under no scope then carries none, as one built under none)."""
    def attrs(found):
        found = dict(found)
        if unscoped and "op_namescope" in found:
            rest = [part for part in found["op_namescope"].split("/")
                    if part != unscoped]
            found["op_namescope"] = "/".join(rest)
            if not rest:
                del found["op_namescope"]
        if unscoped and "__fwd_attrs__" in found:  # a grad op's copy
            found["__fwd_attrs__"] = dict(attrs(found["__fwd_attrs__"]))
        return found

    block = main.global_block()
    ops = [(op.type, sorted((k, list(v)) for k, v in op.inputs.items()),
            sorted((k, list(v)) for k, v in op.outputs.items()),
            sorted((k, repr(v)) for k, v in attrs(op.attrs).items()))
           for op in block.ops]
    params = [(p.name, list(p.shape)) for p in block.all_parameters()]
    text = json.dumps([ops, params]).encode()
    return len(ops), len(params), hashlib.sha256(text).hexdigest()[:16]


def _builders():
    import test_olmoe_model
    import test_qwen3_next_model
    import test_trinity_model
    from paddle_tpu.models import olmoe, qwen3_next, trinity

    return {"trinity": (trinity.trinity_lm_program, test_trinity_model.HP,
                        32),
            "qwen3_next": (qwen3_next.qwen3_next_lm_program,
                           test_qwen3_next_model.HP, 40),
            "olmoe": (olmoe.olmoe_lm_program, test_olmoe_model.HP, 16)}


# taken on PR 65's parent (e0d791b) by this function: `multi_head_attention`'s
# and `rotary_embed`'s new arguments change no op, no attribute, no name and
# no order of Qwen3-Next's and OLMoE's Programs, and of Trinity-Mini's
# nothing but the name scope `rope`, under which `scopes=True` now builds a
# turn of the whole head too (its window layers' `rotary_embed` and its
# gradient say `attn_window/rope` where they said `attn_window`): with that
# name taken off, Trinity-Mini's digests are the parent's
PINNED = {
    ("trinity", False, False): (480, 89, "b95e1e5cdf1854fe"),
    ("trinity", False, True): (189, 89, "d1402fc834b601c6"),
    ("trinity", True, False): (782, 89, "8ccc1a6d2fbf1026"),
    ("trinity", True, True): (390, 89, "31fa9ebe92dbc686"),
    ("qwen3_next", False, False): (463, 73, "91b74749104ad68b"),
    ("qwen3_next", False, True): (190, 73, "255b0f790d4e47ec"),
    ("qwen3_next", True, False): (734, 73, "c62c66225faa3062"),
    ("qwen3_next", True, True): (351, 73, "084ef009b1c4242c"),
    ("olmoe", False, False): (150, 25, "717b45c41cfc2325"),
    ("olmoe", False, True): (61, 25, "f5410d9af48a277b"),
    ("olmoe", True, False): (232, 25, "675bf1a9742ddd54"),
    ("olmoe", True, True): (114, 25, "e57da014a44222e9"),
}


@pytest.mark.parametrize("model, use_bf16, is_test", sorted(PINNED))
def test_the_models_that_share_the_code_build_the_programs_they_built(
        model, use_bf16, is_test):
    build, hp, seq = _builders()[model]
    main = build(hp, seq_len=seq, use_bf16=use_bf16, is_test=is_test)[0]
    moved = model == "trinity"
    assert _digest(main, unscoped="rope" if moved else None) == PINNED[
        (model, use_bf16, is_test)]
    scoped = {(op.type, op.attrs["op_namescope"])
              for op in main.global_block().ops
              if "rope" in op.attrs.get("op_namescope", "").split("/")}
    if moved:
        assert scoped == {("rotary_embed", "attn_window/rope")} | (
            set() if is_test else {("rotary_embed_grad", "attn_window/rope")})


def test_trinitys_refusal_says_what_is_supported():
    """`rotary_embed` has scaled frequencies now; Trinity-Mini publishes
    none, and its builder says so."""
    from paddle_tpu.models import trinity
    import test_trinity_model

    hp = type("Scaled", (test_trinity_model.HP,),
              {"rope_scaling": {"type": "yarn"}})
    with pytest.raises(NotImplementedError) as err:
        trinity.trinity_lm_program(hp, seq_len=32)
    assert "has no scaled frequencies" not in str(err.value)
    assert "yarn" in str(err.value).lower()


# --- the flash kernels under Laguna's window --------------------------------
def _windowed_op_against_dense(t, d, window):
    """The op as a chip takes it (the platform stated, the kernels
    interpreted) against the dense lowering's mask from positions: forward
    and the gradients of q, k and v."""
    keys = jax.random.split(jax.random.PRNGKey(window), 3)
    q, k, v = (jax.random.normal(key, (1, 3, t, d), jnp.float32)
               for key in keys)

    def op(ctx):
        return lambda q, k, v: nn_ops._fused_attention(
            ctx, {"Q": [q], "K": [k], "V": [v]},
            {"causal": True, "window": window})["Out"][0]

    def loss(fn):
        def f(q, k, v):
            o = fn(q, k, v)
            w = jnp.cos(jnp.arange(o.size, dtype=jnp.float32)).reshape(
                o.shape)
            return jnp.sum(o * w)
        return f

    chip, host = op(LowerCtx(platform="tpu")), op(LowerCtx(platform="cpu"))
    np.testing.assert_allclose(np.asarray(chip(q, k, v)),
                               np.asarray(host(q, k, v)),
                               rtol=2e-4, atol=2e-5)
    got = jax.grad(loss(chip), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(host), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("window", [128, 256, 512],
                         ids=["half_a_block", "one_block", "two_blocks"])
def test_the_flash_kernels_are_right_under_a_window_of_half_one_and_two_blocks(
        monkeypatch, window):
    """At T = 1024 in 256-blocks, the ratios of Laguna's 512 window to the
    1024-, 512- and 256-blocks a sequence may be cut in.  Half a block cuts
    the diagonal tile on both sides (class `both`) and leaves no multiple
    of the block on the band's edge; one and two blocks cut the edge tile
    corner to corner."""
    from paddle_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(nn_ops, "_FLASH_BLOCKS", (256,))
    jax.clear_caches()
    t, d = 1024, 64
    tiles = pk._tile_plan(t, 256, 256, window, pk._strip_parts(256))
    assert tiles.both == (window < 256)
    assert (tiles.edge > 1) == (window % 256 == 0)
    assert pk._band_grid(t, t, 256, 256, True, window) == (
        2 if window <= 256 else 3)
    _windowed_op_against_dense(t, d, window)


def test_the_block_the_rule_answers_under_lagunas_window_is_right():
    """The REAL rule, no tuple patched (PR 66): at T = 1024, heads of 128,
    Laguna's 512 window, nn_ops._flash_block answers 512, where it answered
    1024 and one tile of class `both` held the whole sequence: two blocks a
    side, the diagonal's tiles and the edge's in strips, the band as wide
    as the grid."""
    from paddle_tpu.ops import kernel_tuning as kt
    from paddle_tpu.ops import pallas_kernels as pk

    t, d, window = 1024, 128, 512
    assert nn_ops._flash_block(t, window) == 512
    assert pk._tile_counts(t, 512, 512, window) == {
        "whole": 0, "diag": 2, "edge": 1, "both": 0}
    tiles = pk._tile_plan(t, 512, 512, window, pk._strip_parts(512))
    assert (tiles.diag, tiles.edge, tiles.both) == (4, 4, False)
    jax.clear_caches()
    kt.reset_attribution()
    _windowed_op_against_dense(t, d, window)
    steps = kt.attribution()["attention_band_grid"]["steps"]
    assert set(steps) == {"1024x512x512x512"}  # the block that ran
    kt.reset_attribution()
