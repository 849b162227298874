"""Trinity-Mini through Executor.run against models/trinity_reference.py
(plain float32 jax.numpy: attention as an explicit softmax under a mask
built from positions, RoPE written out, experts as a loop over a mask) on
seeded weights, at the small widths of the benchmark configuration's
`rehearse` (a head width of 32 that is not 64 / 4, a window of 8 over 32
positions, the dense layer, three window layers and a full one, 2 of the
router's 8 experts held): the loss, every token's cost and every
parameter's gradient, tight in float32 and at a written tolerance under
the bf16 AMP pass; every deliberate error the benchmark's comparison has
to catch, on weights where it shows; the shares of an expert layer and the
shared expert counted once add up to the uncut layer; the program
verifies; it trains."""

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis
from paddle_tpu.models import gpt2, trinity, trinity_reference as ref

from expert_share import share_through_the_executor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _adapter():
    path = os.path.join(ROOT, "benchmark", "adapters", "trinity_lm.py")
    spec = importlib.util.spec_from_file_location("trinity_lm_adapter", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rehearsal_config():
    """benchmark/configs/trinity_mini.json with its `rehearse` sizes laid
    over the published ones, as benchmark/run.py --rehearse reads it."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity_mini.json")) as f:
        data = json.load(f)
    cfg = {k: v for k, v in data.items() if k != "rehearse"}
    for k, v in data["rehearse"].items():
        cfg[k] = dict(cfg[k], **v) if isinstance(v, dict) else v
    return cfg


ADAPTER = _adapter()
ADAPTER_CFG = _rehearsal_config()
CFG = ADAPTER._arch(ADAPTER_CFG)
HP = type("HP", (trinity.TrinityConfig,), dict(CFG))
SEQ, BATCH = 32, 4
ATTN = ["input_norm.w", "mha_q.w", "mha_k.w", "mha_v.w", "mha_gate.w",
        "mha_q_norm.w", "mha_k_norm.w", "mha_o.w", "post_attn_norm.w",
        "pre_mlp_norm.w"]
DENSE = ["ffn_gate.w", "ffn_up.w", "ffn_out.w", "post_mlp_norm.w"]
BIAS = "moe_expert_bias.b"
MOE = ["moe_router.w", BIAS, "moe_gate_up.w", "moe_down.w",
       "shared_ffn_gate.w", "shared_ffn_up.w", "shared_ffn_out.w",
       "post_mlp_norm.w"]
ORDER = (["emb.w"] + ATTN + DENSE + (ATTN + MOE) * 4
         + ["final_norm.w", "softmax_out.w"])


@functools.lru_cache(maxsize=None)
def _run(use_bf16):
    """(program loss, {param: grad}, reference loss, {param: grad}, the
    program, losses of three training steps, tokens-per-expert of the
    first expert layer, the startup weights) on seeded weights."""
    main, startup, _, fetches = trinity.trinity_lm_program(
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
    hp = trinity.TrinityConfig
    assert (hp.num_hidden_layers, hp.hidden_size, hp.num_attention_heads,
            hp.num_key_value_heads, hp.head_dim, hp.vocab_size) == (
                32, 2048, 32, 4, 128, 200192)
    assert hp.layer_types == (["sliding_attention"] * 3
                              + ["full_attention"]) * 8
    assert (hp.sliding_window, hp.rope_theta, hp.rms_norm_eps) == (
        2048, 10000.0, 1e-5)
    assert (hp.num_experts, hp.num_experts_per_tok, hp.num_shared_experts,
            hp.num_dense_layers, hp.moe_intermediate_size,
            hp.intermediate_size) == (128, 8, 1, 2, 1024, 6144)
    assert (hp.route_scale, hp.route_norm, hp.score_func, hp.n_group,
            hp.topk_group) == (2.826, True, "sigmoid", 1, 1)
    assert hp.mup_enabled and not hp.tie_word_embeddings


def test_the_rehearsal_keeps_what_makes_the_model():
    """A head width that is not hidden / heads, grouped queries, a window
    shorter than the sequence, both kinds of layer, a share."""
    assert HP.head_dim * HP.num_attention_heads != HP.hidden_size
    assert HP.num_attention_heads > HP.num_key_value_heads > 1
    assert 0 < HP.sliding_window < SEQ and SEQ % HP.sliding_window == 0
    assert HP.layer_types == ["sliding_attention"] * 4 + ["full_attention"]
    assert HP.num_local_experts < HP.num_experts and HP.expert_offset


def test_every_parameter_is_created_in_the_references_order():
    block = _run(False)[4].global_block()
    names = [p.name for p in block.all_parameters()]
    assert [n.rsplit("_", 1)[0] for n in names] == ORDER
    shapes = {n: tuple(block.var(n).shape) for n in names}
    assert shapes["mha_q.w_0"] == (64, 4 * 32)     # heads x head_dim
    assert shapes["mha_k.w_0"] == shapes["mha_v.w_0"] == (64, 2 * 32)
    assert shapes["mha_gate.w_0"] == (64, 4 * 32)
    assert shapes["mha_q_norm.w_0"] == shapes["mha_k_norm.w_0"] == (32,)
    assert shapes["mha_o.w_0"] == (4 * 32, 64)
    assert shapes["moe_router.w_0"] == (64, 8)  # the router's full width
    assert shapes[BIAS + "_0"] == (8,)
    assert shapes["moe_gate_up.w_0"] == (2, 64, 64)  # two experts held
    assert shapes["shared_ffn_gate.w_0"] == (64, 32)
    assert shapes["softmax_out.w_0"] == (64, 256)  # the head is its own


def test_the_selection_bias_is_a_buffer_and_every_step_balances_it():
    """Persistable, seeded non-zero, no gradient and no optimizer state;
    one `expert_bias_update` per mixture layer after the optimizer, with
    the builder's `bias_rate` / `bias_max_step` as its attributes where
    given and none where not; not in a forward-only program."""
    main = _run(False)[4]
    block = main.global_block()
    biases = [p for p in block.all_parameters() if p.name.startswith(BIAS)]
    assert len(biases) == 4 and not any(p.trainable for p in biases)
    assert not [n for n in block.vars if BIAS in n and "moment" in n]
    updates = [op for op in block.ops if op.type == "expert_bias_update"]
    assert len(updates) == 4
    assert all("rate" not in op.attrs and "max_step" not in op.attrs
               for op in updates)
    types = [op.type for op in block.ops]
    assert types.index("expert_bias_update") > max(
        i for i, t in enumerate(types) if t == "adam")
    tuned, _, _, _ = trinity.trinity_lm_program(
        HP, seq_len=SEQ, bias_rate=0.03, bias_max_step=0.03)
    assert [(op.attrs["rate"], op.attrs["max_step"])
            for op in tuned.global_block().ops
            if op.type == "expert_bias_update"] == [(0.03, 0.03)] * 4
    eval_main, _, _, _ = trinity.trinity_lm_program(HP, seq_len=SEQ,
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
    2e-6 or less)."""
    _, got, _, want, _, _, _, _ = _run(False)
    names = [n for n in got if n.rsplit("_", 1)[0] == base]
    assert names
    for name in names:
        g, w = np.asarray(got[name]), np.asarray(want[name])
        assert g.shape == w.shape
        assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max(), name


def test_bf16_amp_loss_matches_the_reference_within_its_tolerance():
    """bf16 matmuls against float32 "highest": 1.5e-4 measured on a loss
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
    assert counts.sum() == BATCH * SEQ * HP.num_experts_per_tok
    types = [op.type for op in main.global_block().ops]
    assert types.count("fused_attention") == 5 and types.count("moe_ffn") == 4
    # the dense layer's MLP and the four shared experts
    assert types.count("fused_swiglu") == 5
    assert types.count("fused_linear_xent") == 1


def test_each_kind_of_layer_builds_its_own_attention():
    """A sliding_attention layer: rotary on q and k, a window on the core,
    under attn_window; a full_attention layer: no rotary_embed op at all,
    no window, under attn_full; both: heads of 32 over a hidden size of
    64, the gate under attn_gate, the core under core; the shared expert
    under shared_expert; the embedding scaled by sqrt(64)."""
    block = _run(False)[4].global_block()
    by_scope = {}
    for op in block.ops:
        by_scope.setdefault(op.attrs.get("op_namescope"), []).append(op)
    assert {"attn_window", "attn_window/core", "attn_window/attn_gate",
            "attn_full", "attn_full/core", "attn_full/attn_gate",
            "shared_expert"} <= set(by_scope)
    windows = [op.attrs["window"] for op in by_scope["attn_window/core"]
               if op.type == "fused_attention"]
    assert windows == [8] * 4
    (full,) = [op for op in by_scope["attn_full/core"]
               if op.type == "fused_attention"]
    assert full.attrs["window"] == 0 and full.attrs["causal"]
    assert tuple(block.var(full.inputs["Q"][0]).shape)[1:] == (4, SEQ, 32)
    assert full.attrs["scale"] == 32 ** -0.5
    rotary = [op for op in block.ops if op.type == "rotary_embed"]
    assert len(rotary) == 2 * 4  # q and k of the four window layers
    assert all(op.attrs["op_namescope"] == "attn_window/rope"
               for op in rotary)
    assert {op.type for op in by_scope["attn_full/attn_gate"]} == {
        "sigmoid", "elementwise_mul", "sigmoid_grad", "elementwise_mul_grad"}
    assert "fused_swiglu" in {op.type for op in by_scope["shared_expert"]}
    (scale,) = [op for op in block.ops if op.type == "scale"
                and op.attrs.get("op_role") == "forward"]
    assert scale.attrs["scale"] == 8.0
    for op in block.ops:
        if op.type == "moe_ffn":
            assert op.attrs["routed_scaling_factor"] == 2.826
            assert op.attrs["norm_topk_eps"] == 1e-20


def test_a_window_layers_core_is_counted_over_the_band():
    """utils.flops.program_flops: every grad op counts twice its forward,
    and a core with a window counts Tq x window pairs, never a full
    layer's Tq x Tk."""
    from paddle_tpu.utils.flops import program_flops

    forward, _, _, _ = trinity.trinity_lm_program(HP, seq_len=SEQ,
                                                  is_test=True)
    got = program_flops(forward, batch_hint=BATCH)
    assert program_flops(_run(False)[4], batch_hint=BATCH) == 3.0 * got
    full = type("Full", (HP,), {"sliding_window": SEQ})
    everywhere, _, _, _ = trinity.trinity_lm_program(full, seq_len=SEQ,
                                                     is_test=True)
    pairs = 2.0 * BATCH * 4 * SEQ * (32 + 32)  # QK^T and PV, a key column
    assert program_flops(everywhere, batch_hint=BATCH) - got == (
        4 * pairs * (SEQ - 8))


@pytest.mark.parametrize("key, value, error", [
    ("n_group", 2, NotImplementedError), ("topk_group", 2,
                                          NotImplementedError),
    ("score_func", "softmax", NotImplementedError),
    ("rope_scaling", {"type": "yarn"}, NotImplementedError),
    ("tie_word_embeddings", True, NotImplementedError),
    ("layer_types", ["sliding_attention"] * 4 + ["conv"], ValueError),
    ("num_hidden_layers", 4, ValueError)])
def test_what_the_builder_would_have_to_guess_it_refuses(key, value, error):
    hp = type("Guess", (HP,), {key: value})
    with pytest.raises(error):
        trinity.trinity_lm_program(hp, seq_len=SEQ)


# --- the departures ---------------------------------------------------------
# Weights where every departure shows.  At the startup's normal(0, 0.02)
# the gate's argument is ~0.1 and sigmoid a constant 0.5, which the norm
# on the branch's output removes; the router's scores are all ~0.5, so that
# two of them renormalised are 0.5 again; the embedding is 0.16 beside
# branches of rms 1; and the logits are ~0, the loss log(vocabulary)
# whatever the trunk computes.  A 30 x gate and router, an 8 x embedding,
# larger value / routed / shared projections and a 15 x head make each
# matter.  (Scores need no help: q and k are normalised per head.)
SHOW = {"emb.w": 8.0, "mha_gate.w": 30.0, "mha_v.w": 4.0,
        "moe_router.w": 30.0, "moe_down.w": 4.0, BIAS: 3.0,
        "softmax_out.w": 15.0}


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
    fwd, _, _, fetches = trinity.trinity_lm_program(HP, seq_len=SEQ,
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
    attention goes one head at a time): the same loss (float32, 1e-6),
    and the program's; every token's cost as well."""
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
    assert moved > 1000 * exact, (departure, got, refs[departure])
    assert (moved > ADAPTER.TOLERANCE
            or found[departure]["cost_rms_over_bf16"]
            > ADAPTER.LIMITS["cost_rms_over_bf16"]), (departure, moved,
                                                      found[departure])


def test_an_all_bfloat16_reference_is_told_from_the_exact_one():
    """A float32 program is the exact reference's to 1e-5 of the unit and
    reads the all-bfloat16 one at its own unit, 1, which is over the
    limit."""
    got, refs, _, found, _ = _eval_loss_and_references()
    assert abs(got - refs["all_bfloat16"]) > 1000 * abs(got - refs[None])
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
    assert trinity.EVAL_ROWS not in train.global_block().vars


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
    fwd, _, _, fetches = trinity.trinity_lm_program(HP, seq_len=SEQ,
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
            ADAPTER_CFG, params, batch, "no_post_norms"))
        assert np.isnan(ADAPTER.reference_loss(
            ADAPTER_CFG, params, batch, None, "bfloat16"))
        monkeypatch.setattr(ADAPTER, "LIMITS", {"cost_rms": 1e-12})
        assert np.isnan(ADAPTER.reference_loss(ADAPTER_CFG, params, batch))


# --- the share test ---------------------------------------------------------
SHARES = 16


class Wide(HP):
    """One layer as sixteen chips share it: a router over 16 experts,
    top-4, one expert a chip."""
    num_experts, num_experts_per_tok = SHARES, 4


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


def test_the_sixteen_shares_and_the_shared_expert_once_are_the_layer():
    """Sixteen chips hold one expert each of one layer.  Each routes over
    all sixteen, computes its own expert's part and the WHOLE shared
    expert; the sixteen routed parts plus the shared expert counted ONCE
    are what the uncut reference gives for the layer (adding the sixteen
    outputs would count the shared expert sixteen times), and every chip
    saw the same routing decisions."""
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
    parts = [share_through_the_executor(trinity._experts, Wide, w, offset, 1)
             for offset in range(SHARES)]
    for both, part, counts in parts:
        np.testing.assert_array_equal(counts, want_counts)
        np.testing.assert_allclose(both - part, shared, rtol=1e-4, atol=1e-4)
    assert sum(np.abs(part).max() > 0 for _, part, _ in parts) >= 12
    np.testing.assert_allclose(sum(p for _, p, _ in parts) + shared,
                               routed + shared, rtol=1e-5, atol=5e-5)
    # and one share alone is what the reference gives for that share
    with jax.default_matmul_precision("highest"):
        alone, _ = ref.routed(dict(cfg, expert_offset=5), *args[:3],
                              args[3][5:6], args[4][5:6])
    np.testing.assert_allclose(parts[5][1], alone, rtol=1e-5, atol=1e-5)
