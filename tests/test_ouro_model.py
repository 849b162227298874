"""Ouro through Executor.run against models/ouro_reference.py (plain float32
jax.numpy, the loop a Python loop over the same weights) on seeded weights:
the loss and every parameter's gradient (a shared weight's is the sum over
its four uses), what sharing has to mean in the Program (L parameter sets,
one Adam op and one AMP cast each), the tie to a plain stack at one loop
step, the exit distribution's statistic, and the departures the comparison
has to catch."""

import functools

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis
from paddle_tpu.models import gpt2, ouro, ouro_reference as ref


class HP(ouro.OuroConfig):
    vocab_size = 300
    hidden_size = 64
    intermediate_size = 96
    num_hidden_layers = 2
    num_attention_heads = 2
    num_key_value_heads = 2
    head_dim = 32


class OneStep(HP):
    total_ut_steps = 1
    exit_entropy_beta = 0.0


SEQ, BATCH = 16, 3
LAYER = ["attn_norm.w", "mha_q.w", "mha_k.w", "mha_v.w", "mha_o.w",
         "attn_post_norm.w", "ffn_norm.w", "ffn_gate.w", "ffn_up.w",
         "ffn_out.w", "ffn_post_norm.w"]
PARAMS = (["ouro_emb.w"]
          + ["ouro_l%d.%s" % (i, n) for i in range(HP.num_hidden_layers)
             for n in LAYER]
          + ["ouro_norm.w", "ouro_head.w", "ouro_exit_gate.w",
             "ouro_exit_gate.b"])


def _cfg(hp):
    return {k: getattr(hp, k) for k in dir(hp) if not k.startswith("_")}


@functools.lru_cache(maxsize=None)
def _run(hp=HP, use_bf16=False, gate=True):
    """{loss, grads, params (name -> value), main, stat, steps (three
    training losses)} of one program on seeded weights; `gate` draws a gate
    that is not zero, so that its gradient and the trunk's through q are
    not trivial."""
    main, startup, _, fetches = ouro.ouro_lm_program(
        hp, seq_len=SEQ, lr=1e-3, use_bf16=use_bf16)
    startup.random_seed = main.random_seed = 5
    batch = gpt2.make_fake_lm_batch(BATCH, SEQ, hp, seed=1)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        if gate and hp.total_ut_steps > 1:
            rng = np.random.default_rng(7)
            scope.set("ouro_exit_gate.w", rng.normal(
                0, 0.3, hp.hidden_size).astype("float32"))
            scope.set("ouro_exit_gate.b", np.array([0.2], "float32"))
        params = {p.name: np.asarray(scope.find_var(p.name))
                  for p in main.global_block().all_parameters()}
        out = exe.run(main, feed=batch, fetch_list=[fetches[0]] + [
            main._grad_names[n] for n in params])
        stat = (np.asarray(scope.find_var(ouro.EXIT_STAT))
                if hp.total_ut_steps > 1 else None)
        steps = [float(np.asarray(out[0]).reshape(-1)[0])] + [
            float(np.asarray(exe.run(
                main, feed=batch, fetch_list=[fetches[0]])[0]).reshape(-1)[0])
            for _ in range(2)]
    return {"loss": steps[0], "grads": dict(zip(params, out[1:])),
            "params": params, "main": main, "stat": stat, "steps": steps,
            "batch": batch}


@functools.lru_cache(maxsize=None)
def _want(hp=HP, gate=True, departure=None):
    got = _run(hp, False, gate)
    loss, grads = ref.loss_and_grads(_cfg(hp), list(got["params"].values()),
                                     got["batch"], departure)
    return float(loss), dict(zip(got["params"], grads))


def test_the_program_holds_one_set_of_parameters_for_the_four_steps():
    """L sets of layer weights and one of embedding, final norm, head and
    gate, in the reference's order, whatever total_ut_steps: never 4 L."""
    main = _run()["main"]
    names = [p.name for p in main.global_block().all_parameters()]
    assert names == PARAMS
    assert len(names) == 5 + 11 * HP.num_hidden_layers
    types = [op.type for op in main.global_block().ops]
    # every layer is built total_ut_steps times over them
    assert types.count("fused_attention") == 4 * HP.num_hidden_layers
    assert types.count("fused_swiglu") == 4 * HP.num_hidden_layers
    # the head once, over the four steps' rows stacked
    assert types.count("fused_linear_xent") == 1
    # Adam updates each parameter once
    updated = [op.inputs["Param"][0] for op in main.global_block().ops
               if op.type == "adam"]
    assert sorted(updated) == sorted(PARAMS)


def test_a_parameter_shared_by_name_is_initialised_once():
    _, startup, _, _ = ouro.ouro_lm_program(HP, seq_len=SEQ)
    written = [n for op in startup.global_block().ops
               for n in op.output_arg_names()]
    assert sorted(n for n in written if n in PARAMS) == sorted(PARAMS)


def test_a_name_shared_with_another_shape_is_refused():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8])
        fluid.layers.fc(x, 4, param_attr=fluid.ParamAttr(name="shared.w"))
        with pytest.raises(ValueError, match="shared by name"):
            fluid.layers.fc(x, 5, param_attr=fluid.ParamAttr(name="shared.w"))


def test_float32_loss_matches_the_reference():
    got, (want, _) = _run()["loss"], _want()
    assert abs(got - want) <= 1e-5 * abs(want), (got, want)


@pytest.mark.parametrize("name", PARAMS)
def test_float32_gradient_matches_the_reference(name):
    """A shared weight's gradient is the sum over its four uses, and the
    gate's and the trunk's flow through q: 1e-4 of the gradient's largest
    element (measured: 3e-6 or less)."""
    g, w = np.asarray(_run()["grads"][name]), np.asarray(_want()[1][name])
    assert g.shape == w.shape
    assert np.abs(w).max() > 0
    assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max(), name


@pytest.mark.parametrize("departure", ref.DEPARTURES)
def test_each_departure_moves_the_loss_by_more_than_the_tolerance(departure):
    """What the comparison has to catch: three loop steps instead of four,
    the entropy term left out, the gate reading the state before the final
    norm, the last step weighed by its own gate instead of by what is
    left.  Each moves the reference's loss by far more than the 1e-5 the
    float32 comparison above allows (measured: 1.1e-2, 6.7e-2, 3.1e-2,
    0.21, on a loss of 5.67)."""
    want, _ = _want()
    off, _ = _want(departure=departure)
    assert abs(off - want) > 100 * 1e-5 * abs(want), (departure, off, want)


def test_one_loop_step_is_a_plain_stack_with_a_cross_entropy_loss():
    """total_ut_steps 1, beta 0: no gate, no exit distribution; the loss is
    the cross-entropy of a plain sandwich-norm decoder, computed here from
    the reference's layer alone."""
    import jax
    import jax.numpy as jnp

    got = _run(OneStep)
    names = list(got["params"])
    assert names == PARAMS[:-2]  # no gate
    assert got["stat"] is None
    assert "cumsum" not in [op.type for op in got["main"].global_block().ops]
    cfg, w = _cfg(OneStep), [jnp.asarray(v) for v in got["params"].values()]
    with jax.default_matmul_precision("highest"):
        x = w[0][got["batch"]["ids"]]
        for i in range(OneStep.num_hidden_layers):
            x = ref.layer(cfg, x, w[1 + 11 * i:12 + 11 * i])
        logits = ref.rms_norm(x, w[-2], cfg["rms_norm_eps"]) @ w[-1]
        picked = jnp.take_along_axis(
            logits, jnp.asarray(got["batch"]["labels"])[..., None], -1)[..., 0]
        plain = float((jax.scipy.special.logsumexp(logits, -1)
                       - picked).mean())
    assert abs(got["loss"] - plain) <= 1e-5 * abs(plain)
    want, grads = _want(OneStep)
    assert abs(want - plain) <= 1e-6 * abs(plain)
    for n in names:
        g, r = np.asarray(got["grads"][n]), np.asarray(grads[n])
        assert np.abs(g - r).max() <= 1e-4 * np.abs(r).max(), n


def test_a_zero_gate_exits_at_a_half_a_quarter_and_two_eighths():
    """The model starts from a zero gate: q = 1/2, 1/4, 1/8, 1/8 at every
    token whatever the trunk holds, mean exit step 1.875; q sums to 1."""
    got = _run(gate=False)
    np.testing.assert_allclose(got["stat"], [0.5, 0.25, 0.125, 0.125],
                               rtol=1e-6)
    assert float((np.arange(1, 5) * got["stat"]).sum()) == pytest.approx(1.875)
    cfg = _cfg(HP)
    assert ref.mean_exit_step(cfg, list(got["params"].values()),
                              got["batch"]) == pytest.approx(1.875)
    # and under a gate that is not zero the statistic is the reference's q
    got = _run()
    assert got["stat"].sum() == pytest.approx(1.0, abs=1e-6)
    _, q = ref.token_cost(cfg, list(got["params"].values()),
                          got["batch"]["ids"], got["batch"]["labels"])
    np.testing.assert_allclose(got["stat"], np.asarray(q).mean((1, 2)),
                               rtol=1e-5)
    assert np.abs(got["stat"] - [0.5, 0.25, 0.125, 0.125]).max() > 1e-3


def test_a_saturated_gate_gives_no_nan():
    """The program keeps the exit distribution in logarithms: a gate so
    sure that float32 rounds q to 0 gives a finite loss and gradients."""
    main, startup, _, fetches = ouro.ouro_lm_program(HP, seq_len=SEQ)
    startup.random_seed = main.random_seed = 5
    batch = gpt2.make_fake_lm_batch(BATCH, SEQ, HP, seed=1)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        scope.set("ouro_exit_gate.b", np.array([200.0], "float32"))
        out = exe.run(main, feed=batch, fetch_list=[fetches[0]] + [
            main._grad_names[n] for n in PARAMS])
        stat = np.asarray(scope.find_var(ouro.EXIT_STAT))
    assert all(np.isfinite(np.asarray(o)).all() for o in out)
    np.testing.assert_allclose(stat, [1.0, 0.0, 0.0, 0.0], atol=1e-6)


def test_bf16_amp_casts_every_parameter_once_a_step():
    """A shared f32 master is narrowed once a step, not once a use: as
    many parameter casts as parameters the matmuls read (the norm weights
    and the gate stay f32), and the fan-in of their four uses is one sum
    over the bf16 copy's gradient."""
    block = _run(use_bf16=True)["main"].global_block()
    casts = [op.inputs["X"][0] for op in block.ops
             if op.type == "cast" and op.inputs["X"][0] in PARAMS]
    matmul_weights = [n for n in PARAMS if n.split(".")[-2].split("_")[0] in
                      ("mha", "ffn") and "norm" not in n] + ["ouro_head.w"]
    assert sorted(casts) == sorted(matmul_weights)
    assert len(casts) == len(set(casts)) == 7 * HP.num_hidden_layers + 1


def test_bf16_amp_loss_matches_the_reference_within_its_tolerance():
    """bf16 matmuls against float32 "highest", seen four times over: 1.5e-4
    measured on a loss of 5.67 at these widths."""
    got, (want, _) = _run(use_bf16=True)["loss"], _want()
    assert abs(got - want) <= 2e-3, (got, want)


@pytest.mark.parametrize("use_bf16", [False, True], ids=["f32", "bf16_amp"])
def test_program_verifies_and_trains(use_bf16):
    got = _run(use_bf16=use_bf16)
    diags = analysis.verify_program(got["main"])
    assert not [d for d in diags if d.is_error], diags
    assert got["steps"][2] < got["steps"][1] < got["steps"][0], got["steps"]


def test_an_eval_program_keeps_its_own_exit_statistic_and_every_tokens_rows():
    """An `is_test` program writes `..._eval`, never the training step's
    statistics, and leaves every token's cost after each loop step and its
    log q in `ouro_eval_rows`: the reference's, at float32."""
    main, startup, _, fetches = ouro.ouro_lm_program(HP, seq_len=SEQ,
                                                     is_test=True)
    block = main.global_block()
    assert [n for n in block.vars if n.startswith(ouro.EXIT_STAT)] == [
        ouro.EXIT_STAT + "_eval"]
    got = _run()
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for name, value in got["params"].items():
            scope.set(name, value)
        loss = exe.run(main, feed=got["batch"], fetch_list=[fetches[0]])[0]
        rows = np.asarray(scope.find_var(ouro.EVAL_ROWS))
    assert float(np.asarray(loss).reshape(-1)[0]) == pytest.approx(
        got["loss"], rel=1e-6)
    assert rows.shape == (BATCH, 2 * HP.total_ut_steps, SEQ)
    cfg = _cfg(HP)
    _, q = ref.token_cost(cfg, list(got["params"].values()),
                          got["batch"]["ids"], got["batch"]["labels"])
    np.testing.assert_allclose(np.exp(rows[:, 4:]),
                               np.asarray(q).transpose(1, 0, 2), atol=1e-6)
    # sum_t q_t (l_t + beta log q_t) is the token's cost
    cost = (np.exp(rows[:, 4:]) * (rows[:, :4] + 0.1 * rows[:, 4:])).sum(1)
    assert float(cost.mean()) == pytest.approx(got["loss"], rel=1e-5)


def test_the_training_step_sums_its_exit_distribution_over_its_first_steps():
    """`ouro_exit_step_mean_early` holds the sum of mean_n q_t over the
    steps run so far and their count, and stops at EXIT_STAT_STEPS: three
    steps were run here."""
    got = _run()
    block = got["main"].global_block()
    assert block.var(ouro.EXIT_STAT_EARLY).persistable
    assert ouro.EXIT_STAT_STEPS == 64
    main, startup, _, fetches = ouro.ouro_lm_program(HP, seq_len=SEQ, lr=1e-3)
    startup.random_seed = main.random_seed = 5
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        total = np.zeros(HP.total_ut_steps)
        for step in range(1, 4):
            exe.run(main, feed=got["batch"], fetch_list=[fetches[0]])
            total += np.asarray(scope.find_var(ouro.EXIT_STAT))
            early = np.asarray(scope.find_var(ouro.EXIT_STAT_EARLY))
            assert early[-1] == step
            np.testing.assert_allclose(early[:-1], total, rtol=1e-6)
        # a sum that is full stays as it is
        full = early.copy()
        full[-1] = ouro.EXIT_STAT_STEPS
        scope.set(ouro.EXIT_STAT_EARLY, full)
        exe.run(main, feed=got["batch"], fetch_list=[fetches[0]])
        np.testing.assert_array_equal(
            np.asarray(scope.find_var(ouro.EXIT_STAT_EARLY)), full)


def test_the_loop_steps_and_the_exit_are_name_scopes():
    """ut1 .. ut4 hold the layers and their backward, exit the head, the
    gate and the loss; the fan-in of a weight the four steps share
    belongs to no one step: one n-ary sum a weight, under no name scope."""
    block = _run()["main"].global_block()
    scopes = {}
    for op in block.ops:
        scopes.setdefault(op.attrs.get("op_namescope"), []).append(op.type)
    assert set(scopes) == {None, "ut1", "ut2", "ut3", "ut4", "exit"}
    for t in ("ut1", "ut2", "ut3", "ut4"):
        assert scopes[t].count("fused_attention") == HP.num_hidden_layers
        assert scopes[t].count("fused_attention_grad") == HP.num_hidden_layers
    assert "fused_linear_xent" in scopes["exit"]
    assert "fused_linear_xent_grad" in scopes["exit"]
    assert "cumsum" in scopes["exit"] and "logsigmoid_grad" in scopes["exit"]
    fan_in = [op for op in block.ops if op.type == "sum"
              and op.attrs.get("op_role") == "backward"
              and "op_namescope" not in op.attrs]
    assert all(len(op.inputs["X"]) == 4 for op in fan_in)
    shared = [op for op in fan_in if op.outputs["Out"][0].startswith("ouro_")]
    # every layer weight and the final norm; embedding, head and gate are
    # used once
    assert len(shared) == 11 * HP.num_hidden_layers + 1
    # what else crosses name scopes: the state a loop step hands to the
    # next step (its first norm and its residual) and to the exit (the
    # head's rows and the gate's)
    assert len(fan_in) - len(shared) == HP.total_ut_steps - 1
