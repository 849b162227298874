"""Nemotron-3-Nano through Executor.run against models/nemotron_h_reference.py
(plain float32 jax.numpy: the Mamba-2 scan as the token-by-token
recurrence, the convolution as shifted products, attention as an explicit
softmax, experts as a loop over a mask) on seeded weights, at the small
widths of the benchmark configuration's `rehearse` (the published nine-layer
pattern MEMEM*EME; four Mamba heads of 16 over two groups at state 16; a
head width of 32 that is not 64 / 4; 2 of the router's 8 relu2 experts
held): the loss, every token's cost and every parameter's gradient, tight
in float32 and at a written tolerance under the bf16 AMP pass; the pattern
reader and what it refuses by name; one `expert_bias_update` an expert
layer; every deliberate error the benchmark's comparison has to catch, on
weights where it shows; the program verifies; it trains.  (The sixteen
shares of an expert layer add up to the uncut layer in
tests/test_moe_ffn_op.py.)"""

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
from paddle_tpu.models import gpt2, nemotron_h, nemotron_h_reference as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILE = os.path.join(ROOT, "benchmark", "configs",
                    "nemotron3_nano_30b_a3b.json")


def _adapter():
    path = os.path.join(ROOT, "benchmark", "adapters", "nemotron_h_lm.py")
    spec = importlib.util.spec_from_file_location("nemotron_h_lm_adapter",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rehearsal_config():
    """The configuration file with its `rehearse` sizes laid over the
    published ones, as benchmark/run.py --rehearse reads it."""
    with open(FILE) as f:
        data = json.load(f)
    cfg = {k: v for k, v in data.items() if k != "rehearse"}
    for k, v in data["rehearse"].items():
        cfg[k] = dict(cfg[k], **v) if isinstance(v, dict) else v
    return cfg


ADAPTER = _adapter()
ADAPTER_CFG = _rehearsal_config()
CFG = ADAPTER._arch(ADAPTER_CFG)
HP = type("HP", (nemotron_h.NemotronHConfig,), dict(CFG))
SEQ, BATCH = 40, 4
BIAS = "moe_expert_bias.b"
MAMBA = ["pre_norm.w", "mamba_in.w", "mamba_conv.w", "mamba_conv.b",
         "mamba_dt.b", "mamba_A_log.w", "mamba_D.w", "mamba_norm.w",
         "mamba_out.w"]
ATTN = ["pre_norm.w", "mha_q.w", "mha_k.w", "mha_v.w", "mha_o.w"]
MOE = ["pre_norm.w", "moe_router.w", BIAS, "moe_up.w", "moe_down.w",
       "shared_ffn_up.w", "shared_ffn_out.w"]
LAYER = {"M": MAMBA, "E": MOE, "*": ATTN}
ORDER = (["emb.w"] + [n for ch in "MEMEM*EME" for n in LAYER[ch]]
         + ["final_norm.w", "softmax_out.w"])


@functools.lru_cache(maxsize=None)
def _run(use_bf16):
    """(program loss, {param: grad}, reference loss, {param: grad}, the
    program, losses of three training steps, tokens-per-expert of the
    first expert layer, the startup weights) on seeded weights."""
    main, startup, _, fetches = nemotron_h.nemotron_h_lm_program(
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
    with open(FILE) as f:
        data = json.load(f)
    hp = nemotron_h.NemotronHConfig
    assert (hp.num_hidden_layers, hp.hidden_size, hp.vocab_size) == (
        52, 2688, 131072)
    assert len(hp.hybrid_override_pattern) == 52
    assert hp.hybrid_override_pattern.startswith(
        data["hybrid_override_pattern"])
    assert [hp.hybrid_override_pattern.count(c) for c in "ME*-"] == [
        23, 23, 6, 0]
    assert (hp.n_routed_experts, hp.num_experts_per_tok) == (128, 6)
    # every key the builder reads is as the file (the published config.json)
    # has it, but the three the file cuts
    cut = ("num_hidden_layers", "hybrid_override_pattern", "vocab_size")
    for key in ADAPTER._HP_KEYS:
        if key not in cut:
            assert getattr(hp, key) == data[key], key
    assert data["n_routed_experts"] == 8  # held; the router's width:
    assert data["share"]["router_experts"] == hp.n_routed_experts


def test_the_rehearsal_keeps_what_makes_the_model():
    """The published nine-layer pattern, grouped B and C, a head width
    that is not hidden / heads, grouped queries, a share, a sequence that
    pads to a chunk."""
    assert HP.hybrid_override_pattern == "MEMEM*EME"
    assert HP.mamba_num_heads > HP.n_groups > 1
    assert HP.head_dim * HP.num_attention_heads != HP.hidden_size
    assert HP.num_attention_heads > HP.num_key_value_heads > 1
    assert HP.num_local_experts < HP.n_routed_experts and HP.expert_offset
    assert SEQ % 128


def test_the_pattern_is_read_character_by_character():
    assert nemotron_h.kinds_of(HP) == [
        "mamba2", "experts", "mamba2", "experts", "mamba2", "attention",
        "experts", "mamba2", "experts"]
    whole = nemotron_h.kinds_of(nemotron_h.NemotronHConfig)
    assert [whole.count(k) for k in ("mamba2", "experts", "attention")] == [
        23, 23, 6]


@pytest.mark.parametrize("pattern, layers, error, says", [
    ("MEMEM*EM-", 9, NotImplementedError,
     r"hybrid_override_pattern\[8\] is '-', a dense MLP layer"),
    ("MEMEM*EMX", 9, ValueError, r"\[8\] is 'X': neither M"),
    ("MEMEM*EME", 8, ValueError, "names 9 layers, num_hidden_layers is 8")])
def test_a_pattern_the_builder_cannot_read_is_refused_by_name(
        pattern, layers, error, says):
    hp = type("Guess", (HP,), {"hybrid_override_pattern": pattern,
                               "num_hidden_layers": layers})
    with pytest.raises(error, match=says):
        nemotron_h.nemotron_h_lm_program(hp, seq_len=SEQ)


@pytest.mark.parametrize("key, value, error", [
    ("n_group", 2, NotImplementedError), ("topk_group", 2,
                                          NotImplementedError),
    ("mlp_hidden_act", "silu", NotImplementedError),
    ("mamba_proj_bias", True, NotImplementedError),
    ("chunk_size", 256, NotImplementedError),
    ("n_groups", 3, ValueError),
    ("tie_word_embeddings", True, NotImplementedError)])
def test_what_the_builder_would_have_to_guess_it_refuses(key, value, error):
    hp = type("Guess", (HP,), {key: value})
    with pytest.raises(error):
        nemotron_h.nemotron_h_lm_program(hp, seq_len=SEQ)


def test_every_parameter_is_created_in_the_references_order():
    block = _run(False)[4].global_block()
    names = [p.name for p in block.all_parameters()]
    assert [n.rsplit("_", 1)[0] for n in names] == ORDER
    shapes = {n: tuple(block.var(n).shape) for n in names}
    # [z | xBC | dt]: 64 + (64 + 2 x 2 x 16) + 4
    assert shapes["mamba_in.w_0"] == (64, 64 + 128 + 4)
    assert shapes["mamba_conv.w_0"] == (128, 4)
    assert shapes["mamba_conv.b_0"] == (128,)
    assert shapes["mamba_dt.b_0"] == shapes["mamba_A_log.w_0"] == (4,)
    assert shapes["mamba_D.w_0"] == (4,)
    assert shapes["mamba_norm.w_0"] == (2, 32)  # a gain a group
    assert shapes["mamba_out.w_0"] == (64, 64)
    assert shapes["mha_q.w_0"] == (64, 4 * 32)  # heads x head_dim
    assert shapes["mha_k.w_0"] == shapes["mha_v.w_0"] == (64, 2 * 32)
    assert shapes["moe_router.w_0"] == (64, 8)  # the router's full width
    assert shapes[BIAS + "_0"] == (8,)
    assert shapes["moe_up.w_0"] == (2, 64, 32)  # two held, NO gate half
    assert shapes["moe_down.w_0"] == (2, 32, 64)
    assert shapes["shared_ffn_up.w_0"] == (64, 64)
    assert shapes["softmax_out.w_0"] == (64, 1024)  # the head is its own


def test_the_startup_draws_the_published_mixers_initialisation():
    """A = exp(A_log) in [1, 16]; dt = softplus(dt_bias) in [0.001, 0.1]
    (never under time_step_floor); D ones, the convolution's bias zero,
    every gain one; the projections into the residual 1 / sqrt(9)
    narrower."""
    values = dict(_run(False)[7])
    a = np.exp(values["mamba_A_log.w_0"])
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 0
    dt = np.log1p(np.exp(values["mamba_dt.b_2"]))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    assert (values["mamba_D.w_1"] == 1).all()
    assert not values["mamba_conv.b_3"].any()
    taps = values["mamba_conv.w_0"]  # nn.Conv1d's default at four taps
    assert np.abs(taps).max() <= 0.5 and taps.std() == pytest.approx(
        0.5 / 3 ** 0.5, rel=0.1)
    assert (values["mamba_norm.w_0"] == 1).all()
    assert (values["pre_norm.w_4"] == 1).all()
    wide, narrow = values["mamba_in.w_0"].std(), values["mamba_out.w_0"].std()
    assert wide == pytest.approx(0.02, rel=0.1)
    assert narrow == pytest.approx(0.02 / 3, rel=0.1)
    for base in ("mha_o.w_0", "moe_down.w_0", "shared_ffn_out.w_0"):
        assert values[base].std() == pytest.approx(0.02 / 3, rel=0.15), base


def test_the_selection_bias_is_a_buffer_and_every_expert_layer_balances_it():
    """Persistable, seeded non-zero, no gradient and no optimizer state;
    ONE `expert_bias_update` an expert layer after the optimizer, with the
    issue's rate and bound; not in a forward-only program."""
    main = _run(False)[4]
    block = main.global_block()
    biases = [p for p in block.all_parameters() if p.name.startswith(BIAS)]
    assert len(biases) == 4 and not any(p.trainable for p in biases)
    assert not [n for n in block.vars if BIAS in n and "moment" in n]
    updates = [op for op in block.ops if op.type == "expert_bias_update"]
    assert [(op.attrs["rate"], op.attrs["max_step"]) for op in updates] == [
        (0.03, 0.03)] * 4
    assert sorted(op.inputs["ExpertBias"][0] for op in updates) == sorted(
        p.name for p in biases)
    types = [op.type for op in block.ops]
    assert types.index("expert_bias_update") > max(
        i for i, t in enumerate(types) if t == "adam")
    eval_main, _, _, _ = nemotron_h.nemotron_h_lm_program(HP, seq_len=SEQ,
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
    in another order (the scan chunkwise against token by token), 1e-4 of
    the gradient's largest element."""
    _, got, _, want, _, _, _, _ = _run(False)
    names = [n for n in got if n.rsplit("_", 1)[0] == base]
    assert names
    for name in names:
        g, w = np.asarray(got[name]), np.asarray(want[name])
        assert g.shape == w.shape
        assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max(), name


def test_bf16_amp_loss_matches_the_reference_within_its_tolerance():
    """bf16 matmuls and bf16 x, B, C into the scan against float32
    "highest": 1e-4 measured on a loss of 6.93 at these widths."""
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
    assert types.count("mamba2_scan") == 4 and types.count("moe_ffn") == 4
    assert types.count("fused_attention") == 1
    assert types.count("causal_conv") == 4 and "rotary_embed" not in types
    assert types.count("fused_linear_xent") == 1
    moes = [op for op in main.global_block().ops if op.type == "moe_ffn"]
    assert all(op.attrs["expert_act"] == "relu2" for op in moes)


def test_each_layer_is_one_norm_one_block_and_one_add():
    """Nine layers and the final norm: ten norms of the hidden width (the
    four grouped ones inside the mixers apart), and the scopes the metrics
    read."""
    block = _run(False)[4].global_block()
    forward = [op for op in block.ops if not op.type.endswith("_grad")]
    hidden = [op for op in forward if op.type == "rms_norm"
              and tuple(block.var(op.inputs["Scale"][0]).shape) == (64,)]
    grouped = [op for op in forward if op.type == "rms_norm"
               and len(block.var(op.inputs["Scale"][0]).shape) == 2]
    assert len(hidden) == 10 and len(grouped) == 4
    scopes = {op.attrs.get("op_namescope") for op in forward}
    for name in ("mamba2/in_proj", "mamba2/conv", "mamba2/core",
                 "mamba2/norm", "mamba2/out_proj", "attn_full/core",
                 "shared_expert"):
        assert any(s and s.strip("/") == name for s in scopes), (name, scopes)
    (core,) = {op.attrs["op_namescope"].strip("/") for op in forward
               if op.type == "mamba2_scan"}
    assert core == "mamba2/core"


# --- the departures ---------------------------------------------------------
# Weights where every departure shows.  At the startup's normal(0, 0.02)
# (and a third of it into the residual) the branches are small beside an
# embedding of 0.02 too, the router's scores all ~0.5, attention's scores
# ~0 (so that turning q and k changes nothing) and the logits ~0, the loss
# log(vocabulary) whatever the trunk computes; and the step dt is 0.001 to
# 0.1, so that a Mamba-2 layer is D x and a few percent of state.  Larger
# projections make each part matter, and a tenth of dt_bias (about -4.6 at
# the startup) a step near 0.5 and twice the filters (at these widths the
# convolution's input is 0.16, not the 1.0 of the published ones) let the
# state carry most of y.
SHOW = {"emb.w": 8.0, "mamba_in.w": 3.0, "mamba_conv.w": 2.0,
        "mamba_dt.b": 0.1,
        "mamba_out.w": 9.0, "mha_q.w": 20.0,
        "mha_k.w": 20.0, "mha_v.w": 3.0, "mha_o.w": 9.0,
        "moe_router.w": 30.0, BIAS: 3.0, "moe_up.w": 6.0, "moe_down.w": 12.0,
        "shared_ffn_up.w": 4.0, "shared_ffn_out.w": 6.0,
        "softmax_out.w": 15.0}
# what rounds where the exact model does not: told by the paired costs
ROUNDINGS = ("state_bf16", "dt_bf16")
WRONG = tuple(d for d in ADAPTER.DEPARTURES if d not in ROUNDINGS)


@functools.lru_cache(maxsize=None)
def _eval_loss_and_references():
    """The forward loss of the program on the SHOW weights, the adapter's
    reference on the same weights (exact, with each of its deliberate
    errors, and all in bfloat16), compared as the harness compares them
    (inside the scope the forward-only program ran in), and the model's
    reference: (program loss, {name: reference loss}, the model's
    reference's loss and rows, {name: paired readings}, the program's
    rows)."""
    params = [(n, v * SHOW.get(n.rsplit("_", 1)[0], 1.0))
              for n, v in _run(False)[7]]
    fwd, _, _, fetches = nemotron_h.nemotron_h_lm_program(HP, seq_len=SEQ,
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


def _over_a_limit(found):
    return any(found[k] > ADAPTER.LIMITS[k] for k in ADAPTER.LIMITS)


def test_the_adapters_reference_is_the_models_reference():
    """Two statements of the same equations, written apart: the same loss
    (float32, 1e-6), and the program's; every token's cost as well."""
    got, refs, (want, want_rows), _, rows = _eval_loss_and_references()
    assert refs[None] == pytest.approx(want, rel=1e-6)
    assert got == pytest.approx(want, rel=1e-5)
    np.testing.assert_allclose(rows, want_rows, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("departure", WRONG)
def test_each_wrong_model_moves_the_loss_where_the_exact_reference_does_not(
        departure):
    """The program against the reference with ONE deliberate error, on the
    SHOW weights, in float32: each moves the loss by a thousand times what
    the exact reference differs by, and the cell's comparison fails it: the
    loss is outside the adapter's TOLERANCE or the paired costs are over
    their limit."""
    got, refs, _, found, _ = _eval_loss_and_references()
    exact = abs(got - refs[None])
    assert exact <= 2e-5
    moved = abs(got - refs[departure])
    assert moved > 1000 * exact, (departure, got, refs[departure])
    assert moved > ADAPTER.TOLERANCE or _over_a_limit(found[departure]), (
        departure, moved, found[departure])


def test_an_all_bfloat16_reference_is_told_from_the_exact_one():
    """A float32 program is the exact reference's to 1e-3 of the unit and
    reads the all-bfloat16 one at its own unit, 1, which is over the
    limit."""
    _, _, _, found, _ = _eval_loss_and_references()
    for reading in ("cost_rms_over_bf16", "cost_median_over_bf16"):
        assert found[None][reading] < 0.01
        assert found["all_bfloat16"][reading] == pytest.approx(1.0, abs=2e-2)
    # the median is the limit that tells the precision below: the rows'
    # differences are heavy-tailed on the chip (PERF.md section 4), and the
    # root mean square's limit is there for the wrong models
    assert ADAPTER.LIMITS["cost_median_over_bf16"] < 0.9
    assert _over_a_limit(found["all_bfloat16"])


def test_the_forward_only_program_leaves_what_the_comparison_pairs():
    """Every token's cost stays in the scope of an `is_test` program; in
    float32 the rows are the exact reference's to 1e-4."""
    found = _eval_loss_and_references()[3][None]
    assert found["cost_rms"] <= 1e-4
    train = _run(False)[4]
    assert nemotron_h.EVAL_ROWS not in train.global_block().vars


@pytest.mark.parametrize("departure", WRONG + ("all_bfloat16",))
def test_each_departure_moves_the_paired_costs(departure):
    """Token by token nothing averages away: on the SHOW weights each
    wrong reference, and the exact one a precision down, differs from the
    program's rows by more than a thousand times what the exact one does,
    and reads over the comparison's limit."""
    found = _eval_loss_and_references()[3]
    assert found[departure]["cost_rms"] > max(
        1e-3, 1000 * found[None]["cost_rms"]), found[departure]
    assert _over_a_limit(found[departure]), found[departure]


@pytest.mark.parametrize("departure", ROUNDINGS)
def test_a_float32_part_rounded_to_bfloat16_shows_in_the_paired_costs(
        departure):
    """The carried state, or dt and dt A, in bfloat16 where the model says
    float32: the rows move by ten times what the exact reference's differ
    by from the float32 program's (at full width on the chip the reading
    is tools/nemotron_h_departures.py's)."""
    found = _eval_loss_and_references()[3]
    assert found[departure]["cost_rms"] > 10 * found[None]["cost_rms"], (
        found[departure], found[None])


def test_a_paired_reading_over_its_limit_reaches_the_harness_as_nan(
        monkeypatch):
    """loops/train.py takes one float: a reading over its limit makes it
    NaN, which no tolerance admits; without a program's rows in the scope
    the loss comes back as it is."""
    params = [(n, v * SHOW.get(n.rsplit("_", 1)[0], 1.0))
              for n, v in _run(False)[7]]
    batch = gpt2.make_fake_lm_batch(BATCH, SEQ, HP, seed=1)
    fwd, _, _, fetches = nemotron_h.nemotron_h_lm_program(HP, seq_len=SEQ,
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
            ADAPTER_CFG, params, batch, "no_d_skip"))
        assert np.isnan(ADAPTER.reference_loss(
            ADAPTER_CFG, params, batch, None, "bfloat16"))
        monkeypatch.setattr(ADAPTER, "LIMITS", {"cost_rms": 1e-12})
        assert np.isnan(ADAPTER.reference_loss(ADAPTER_CFG, params, batch))
        with pytest.raises(ValueError, match="unknown departure"):
            ADAPTER.reference(ADAPTER_CFG, params, batch, "no_such_error")
